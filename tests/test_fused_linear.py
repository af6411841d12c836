"""Fused single-launch W4A4 linear (kernels/bcq_linear.py) validation.

Contracts:
* the Pallas fused kernel (interpret mode) is BIT-exact with the existing
  two-launch quantize→matmul Pallas path at matching tile sizes,
* both agree with the pure-jnp oracle ``ref.fused_linear_ref``,
* the qdense packed path is token-for-token identical through
  ``greedy_generate`` whether linears run fused or via the in-graph
  decode_packed_weight + einsum,
* ``interpret=None`` auto-detects the backend (no silent interpret mode on
  a real TPU; interpret everywhere else).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcq, ptq
from repro.core.bcq import BCQConfig
from repro.kernels import bcq_linear, ops, ref
from repro.kernels.bcq_linear import bcq_linear_pallas
from repro.kernels.bcq_quantize import bcq_quantize_pallas

CFGS = [
    BCQConfig(block_len=4, array_len=32, n_codebooks=4),
    BCQConfig(),  # paper default g64 / L_b 8 / N_c 8
    BCQConfig(block_len=8, array_len=64, n_codebooks=16),
]


def _codebooks(cfg, seed=0):
    data = jax.random.laplace(jax.random.PRNGKey(seed), (60000,))
    return bcq.fit_lobcq(data, cfg, iters=4, max_blocks=4096).as_jnp()


def _two_launch(x, pw, cb, cfg, tiles):
    tm, tn, tk = tiles
    a = ops.quantize(x, cb, cfg, impl="pallas", tile_m=tm, tile_k=tk)
    return ops.matmul(a, pw, cb, cfg, impl="pallas", tile_m=tm, tile_n=tn, tile_k=tk)


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
@pytest.mark.parametrize("tiles", [(64, 64, 256), (32, 64, 128), (128, 128, 512)])
def test_fused_bitexact_with_two_launch(cfg, tiles):
    """Acceptance: w4a4_linear_fused ≡ quantize∘matmul, bit for bit."""
    if tiles[2] % cfg.array_len:
        pytest.skip("tile_k must be a multiple of L_A")
    m, n, k = 128, 192, 512
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    w = jax.random.t(jax.random.PRNGKey(2), 3.0, (n, k))
    cb = _codebooks(cfg)
    tm, tn, tk = tiles
    pw = ops.quantize(w, cb, cfg, impl="pallas", tile_m=tn, tile_k=tk)
    o_fused = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=tm, tile_n=tn, tile_k=tk
    )
    o_two = _two_launch(x, pw, cb, cfg, tiles)
    np.testing.assert_array_equal(np.asarray(o_fused), np.asarray(o_two))


@pytest.mark.parametrize("k_block", [256, 512])
def test_fused_k_blocks_bitexact_with_whole_k(monkeypatch, k_block):
    """K split over the grid (the out block carrying the f32 accumulator
    from one K block to the next, weights decoded at every step) equals
    the whole-K step and the two-launch path bit for bit, over two M
    tiles.  The K blocks come from the kernel's own choice, with the VMEM
    budget lowered to what a ``k_block`` step states."""
    cfg = BCQConfig()
    m, n, k = 256, 128, 1024
    x = jax.random.normal(jax.random.PRNGKey(11), (m, k))
    w = jax.random.t(jax.random.PRNGKey(12), 3.0, (n, k))
    cb = _codebooks(cfg)
    pw = ops.quantize(w, cb, cfg, impl="ref")
    s_x = bcq.tensor_scale(x, cfg)

    def fused():
        bcq_linear_pallas.clear_cache()  # the K extent is chosen at trace time
        return np.asarray(bcq_linear_pallas(
            x, pw.idx_packed, pw.sel_packed, pw.inv_scale, cb, s_x, cfg,
            tile_m=128, tile_n=128, tile_k=256,
        ))

    assert bcq_linear.fit_k_block(k, 128, 128, 256, cfg) == k
    whole = fused()
    monkeypatch.setattr(bcq_linear, "VMEM_BUDGET", bcq_linear.vmem_bytes(128, 128, k, k_block, 256, cfg))
    assert bcq_linear.fit_k_block(k, 128, 128, 256, cfg) == k_block
    try:
        np.testing.assert_array_equal(fused(), whole)
    finally:
        bcq_linear_pallas.clear_cache()
    np.testing.assert_array_equal(whole, np.asarray(_two_launch(x, pw, cb, cfg, (128, 128, 256))))


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
def test_fused_matches_ref_oracle(cfg):
    """Multi-K-tile shape (exercises the decoded-weight VMEM cache) vs the
    jnp oracle and the fake-quant expectation."""
    m, n, k = 96, 130, 4 * 256  # ragged rows/cols, 4 K tiles
    x = jax.random.normal(jax.random.PRNGKey(3), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(4), (n, k)) * 0.1
    cb = _codebooks(cfg)
    pw = ops.quantize(w, cb, cfg, impl="ref")
    o_ref = ops.w4a4_linear_fused(x, pw, cb, cfg, impl="ref")
    o_pl = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256
    )
    assert o_pl.shape == (m, n)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), rtol=1e-5, atol=1e-4)
    expect = bcq.fake_quant(x, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_fused_ref_equals_two_launch_ref():
    """The CPU fallback composes quantize_ref+matmul_ref — identical to the
    two-launch ref path (so the packed model path changes no ref numerics)."""
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 40, 256), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(6), (96, 256))
    pw = ops.quantize(w, cb, cfg, impl="ref")
    o_fused = ops.w4a4_linear_fused(x, pw, cb, cfg, impl="ref")
    o_two = ops.w4a4_linear(x, pw, cb, cfg, impl="ref")
    assert o_fused.shape == (3, 40, 96) and o_fused.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(o_fused), np.asarray(o_two))


def test_fused_ragged_shapes_pad_correctly():
    cfg = BCQConfig(block_len=4, array_len=32, n_codebooks=4)
    cb = _codebooks(cfg)
    m, n, k = 100, 70, 320  # none tile-aligned (K still % L_A)
    x = jax.random.normal(jax.random.PRNGKey(7), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(8), (n, k))
    pw = ops.quantize(w, cb, cfg, impl="pallas", tile_m=64, tile_k=64)
    o_pl = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=64
    )
    assert o_pl.shape == (m, n)
    o_two = _two_launch(x, pw, cb, cfg, (64, 64, 64))
    np.testing.assert_array_equal(np.asarray(o_pl), np.asarray(o_two))


def test_interpret_autodetect_off_tpu():
    """interpret=None (the new default) resolves per backend — a bare call
    off-TPU runs interpret mode instead of failing to lower."""
    from repro.kernels.common import resolve_interpret

    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (128, 512))
    s_x = bcq.tensor_scale(x, cfg)
    ip, sp, rt = bcq_quantize_pallas(x, cb, s_x, cfg)  # no interpret arg
    ip2, sp2, rt2 = ref.quantize_ref(x, cb, cfg, s_x)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(rt2))


# ------------------------------------------------------ model-path regression
def test_qdense_packed_fused_vs_unfused_greedy():
    """quant_mode='packed' serving is token-for-token identical with the
    fused kernel path on vs off, end-to-end through greedy_generate."""
    from repro.configs.base import get_smoke
    from repro.data.pipeline import DataConfig, batch_at
    from repro.models import zoo
    from repro.models.layers import Runtime
    from repro.serving.generate import greedy_generate

    arch = get_smoke("gpt3_126m")
    bcfg = BCQConfig()
    cb = _codebooks(bcfg)
    rt0 = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
    params = zoo.build(arch, rt0).init(jax.random.PRNGKey(0))
    packed = ptq.pack_params(params, cb, bcfg)
    packed["codebooks"] = cb
    prompts = batch_at(DataConfig(vocab=arch.vocab, seq_len=16, global_batch=2), 0)["tokens"]
    toks = {}
    for fused in (True, False):
        rt = Runtime(
            quant_mode="packed", bcq_cfg=bcfg, compute_dtype=jnp.float32,
            param_dtype=jnp.float32, fused_linear=fused,
        )
        api = zoo.build(arch, rt)
        toks[fused] = np.asarray(greedy_generate(api, packed, prompts, 6, 32))
    np.testing.assert_array_equal(toks[True], toks[False])


def test_qdense_shared_packed_nonbcq_act_keeps_unfused_path():
    """act_format='none' (W4A16) & friends are not implemented by the fused
    kernel — the shared packed path must keep honoring them (fused flag on
    or off gives identical outputs)."""
    import dataclasses as dc

    from repro.models.layers import Runtime, pack_weight, qdense_shared

    bcfg = BCQConfig()
    cb = _codebooks(bcfg)
    k, n = 128, 64
    x = jax.random.normal(jax.random.PRNGKey(12), (4, k))
    w = jax.random.normal(jax.random.PRNGKey(13), (k, n)) * 0.05
    p = {"kernel_packed": pack_weight(w, bcfg, cb)}
    base = Runtime(
        quant_mode="packed", bcq_cfg=bcfg, compute_dtype=jnp.float32,
        param_dtype=jnp.float32, act_format="none",
    )
    (y_fused,) = qdense_shared(x, [p], dc.replace(base, fused_linear=True), cb)
    (y_unf,) = qdense_shared(x, [p], dc.replace(base, fused_linear=False), cb)
    np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_unf))


def test_moe_packed_fused_matches_unfused():
    """Expert GEMMs: fused per-expert kernel vs decode+einsum (shared global
    activation s_X keeps the quantization identical)."""
    import dataclasses as dc

    from repro.models import moe as moe_lib
    from repro.models.layers import Runtime, pack_weight

    bcfg = BCQConfig()
    cb = _codebooks(bcfg)
    e, c, k, n = 2, 8, 128, 64
    xe = jax.random.normal(jax.random.PRNGKey(10), (e, c, k))
    wk = jax.random.normal(jax.random.PRNGKey(11), (e, k, n)) * 0.05
    packed = jax.vmap(lambda w: pack_weight(w, bcfg, cb))(wk)
    wp = {"kernel_packed": packed}
    base = Runtime(
        quant_mode="packed", bcq_cfg=bcfg, compute_dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    o_fused = moe_lib._expert_matmul(xe, wp, dc.replace(base, fused_linear=True), cb)
    o_unf = moe_lib._expert_matmul(xe, wp, dc.replace(base, fused_linear=False), cb)
    assert o_fused.shape == (e, c, n)
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_unf), rtol=1e-5, atol=1e-5)
