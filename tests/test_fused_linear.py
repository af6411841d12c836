"""Fused single-launch W4A4 linear (kernels/bcq_linear.py) validation.

Contracts:
* the Pallas fused kernel (interpret mode) is BIT-exact with the jnp
  oracle's pieces — ``ref.quantize_ref`` encode, ``ref.decode_ref`` of
  both operands — accumulated over K one ``tile_k`` tile at a time, and
  agrees with ``ref.fused_linear_ref`` (one dot over K) to f32 rounding,
* the qdense / MoE packed paths give the same tokens and outputs whether
  the linears run the kernel (``fused_linear=True``) or the oracle
  (``fused_linear=False``),
* ``quant_mode='packed'`` rejects activation formats other than BCQ,
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

CFGS = [
    BCQConfig(block_len=4, array_len=32, n_codebooks=4),
    BCQConfig(),  # paper default g64 / L_b 8 / N_c 8
    BCQConfig(block_len=8, array_len=64, n_codebooks=16),
]


def _codebooks(cfg, seed=0):
    data = jax.random.laplace(jax.random.PRNGKey(seed), (60000,))
    return bcq.fit_lobcq(data, cfg, iters=4, max_blocks=4096).as_jnp()


def _tiled_oracle(x, pw, cb, cfg, tiles):
    """The oracle's encode and decode, contracted in the kernel's
    (tile_m, tile_n) output blocks, each accumulated over K one
    ``tile_k`` tile at a time (K must be a multiple of it)."""
    tm, tn, tk = tiles
    s_x = bcq.tensor_scale(x, cfg)
    idx, sel, ratio = ref.quantize_ref(x, cb, cfg, s_x)
    a = ref.decode_ref(idx, sel, ref.inv_scale(ratio, s_x), cb, cfg)
    w = ref.decode_ref(pw.idx_packed, pw.sel_packed, pw.inv_scale, cb, cfg)
    (m, k), n = a.shape, w.shape[0]
    a = jnp.pad(a, ((0, -m % tm), (0, 0)))
    w = jnp.pad(w, ((0, -n % tn), (0, 0)))
    out = np.zeros((a.shape[0], w.shape[0]), np.float32)
    for m0 in range(0, a.shape[0], tm):
        for n0 in range(0, w.shape[0], tn):
            acc = jnp.zeros((tm, tn), jnp.float32)
            for k0 in range(0, k, tk):
                acc = acc + jax.lax.dot_general(
                    a[m0 : m0 + tm, k0 : k0 + tk], w[n0 : n0 + tn, k0 : k0 + tk],
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
            out[m0 : m0 + tm, n0 : n0 + tn] = np.asarray(acc)
    return out[:m, :n]


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
@pytest.mark.parametrize("tiles", [(64, 64, 256), (32, 64, 128), (128, 128, 512)])
def test_fused_bitexact_with_oracle(cfg, tiles):
    """Acceptance: w4a4_linear_fused ≡ the oracle's encode and decode,
    accumulated over K in ``tile_k`` tiles, bit for bit."""
    m, n, k = 128, 192, 512
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    w = jax.random.t(jax.random.PRNGKey(2), 3.0, (n, k))
    cb = _codebooks(cfg)
    tm, tn, tk = tiles
    pw = ops.quantize(w, cb, cfg)
    o_fused = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=tm, tile_n=tn, tile_k=tk
    )
    np.testing.assert_array_equal(np.asarray(o_fused), _tiled_oracle(x, pw, cb, cfg, tiles))


@pytest.mark.parametrize("k_block", [256, 512])
def test_fused_k_blocks_bitexact_with_whole_k(monkeypatch, k_block):
    """K split over the grid (the out block carrying the f32 accumulator
    from one K block to the next, weights decoded at every step) equals
    the whole-K step and the tiled oracle bit for bit, over two M tiles.
    The K blocks come from the kernel's own choice, with the VMEM budget
    lowered to what a ``k_block`` step states."""
    cfg = BCQConfig()
    m, n, k = 256, 128, 1024
    x = jax.random.normal(jax.random.PRNGKey(11), (m, k))
    w = jax.random.t(jax.random.PRNGKey(12), 3.0, (n, k))
    cb = _codebooks(cfg)
    pw = ops.quantize(w, cb, cfg)
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
    np.testing.assert_array_equal(whole, _tiled_oracle(x, pw, cb, cfg, (128, 128, 256)))


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
def test_fused_matches_ref_oracle(cfg):
    """Multi-K-tile shape (exercises the decoded-weight VMEM cache) vs the
    jnp oracle and the fake-quant expectation."""
    m, n, k = 96, 130, 4 * 256  # ragged rows/cols, 4 K tiles
    x = jax.random.normal(jax.random.PRNGKey(3), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(4), (n, k)) * 0.1
    cb = _codebooks(cfg)
    pw = ops.quantize(w, cb, cfg)
    o_ref = ops.w4a4_linear_fused(x, pw, cb, cfg, impl="ref")
    o_pl = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256
    )
    assert o_pl.shape == (m, n)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), rtol=1e-5, atol=1e-4)
    expect = bcq.fake_quant(x, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_fused_ragged_shapes_pad_correctly():
    cfg = BCQConfig(block_len=4, array_len=32, n_codebooks=4)
    cb = _codebooks(cfg)
    m, n, k = 100, 70, 320  # none tile-aligned (K still % L_A)
    x = jax.random.normal(jax.random.PRNGKey(7), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(8), (n, k))
    pw = ops.quantize(w, cb, cfg)
    o_pl = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=64
    )
    assert o_pl.shape == (m, n)
    np.testing.assert_array_equal(np.asarray(o_pl), _tiled_oracle(x, pw, cb, cfg, (64, 64, 64)))


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
    w = jax.random.normal(jax.random.PRNGKey(10), (128, 512)) * 0.1
    pw = ops.quantize(w, cb, cfg)
    s_x = bcq.tensor_scale(x, cfg)
    got = bcq_linear_pallas(  # no interpret arg
        x, pw.idx_packed, pw.sel_packed, pw.inv_scale, cb, s_x, cfg
    )
    np.testing.assert_array_equal(np.asarray(got), _tiled_oracle(x, pw, cb, cfg, (128, 128, 512)))


# ------------------------------------------------------ model-path regression
@pytest.fixture
def kernel_route(monkeypatch):
    """``fused_linear=True`` runs the interpret-mode kernel off the chip
    (not the oracle twice); yields the list of kernel traces, so a test
    can check that the kernel route really ran it."""
    traced = []

    def counted(*args, **kw):
        traced.append(args[0].shape)
        return bcq_linear_pallas(*args, **kw)

    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "bcq_linear_pallas", counted)
    jax.clear_caches()  # no trace of the oracle route may stand in
    yield traced
    jax.clear_caches()


def test_qdense_packed_fused_vs_unfused_greedy(kernel_route):
    """quant_mode='packed' serving is token-for-token identical with the
    linears on the kernel (fused_linear=True) or on the oracle
    (fused_linear=False), end-to-end through greedy_generate."""
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
    toks, traced = {}, {}
    for fused in (True, False):
        rt = Runtime(
            quant_mode="packed", bcq_cfg=bcfg, compute_dtype=jnp.float32,
            param_dtype=jnp.float32, fused_linear=fused,
        )
        api = zoo.build(arch, rt)
        toks[fused] = np.asarray(greedy_generate(api, packed, prompts, 6, 32))
        traced[fused] = len(kernel_route)
    assert traced[True] > 0 and traced[False] == traced[True]  # no kernel on the oracle route
    np.testing.assert_array_equal(toks[True], toks[False])


@pytest.mark.parametrize("act_format", ["none", "mx4", "mxfp4", "vsq", "int4"])
def test_packed_rejects_non_bcq_act_format(act_format):
    """The fused linear encodes BCQ activations only, so quant_mode='packed'
    refuses every other act_format at construction, naming both; the
    fake-quant modes keep serving them."""
    from repro.models.layers import Runtime

    with pytest.raises(ValueError, match=f"quant_mode='packed'.*act_format='{act_format}'"):
        Runtime(quant_mode="packed", act_format=act_format)
    for mode in ("fake", "fake_full"):
        assert Runtime(quant_mode=mode, act_format=act_format).act_format == act_format


def test_moe_packed_fused_matches_unfused(kernel_route):
    """Expert GEMMs: the fused per-expert kernel vs the per-expert oracle
    (one activation s_X over all experts' tokens on both routes)."""
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
    o_unf = moe_lib._expert_matmul(xe, wp, dc.replace(base, fused_linear=False), cb)
    assert not kernel_route
    o_fused = moe_lib._expert_matmul(xe, wp, dc.replace(base, fused_linear=True), cb)
    assert kernel_route
    assert o_fused.shape == (e, c, n)
    np.testing.assert_allclose(np.asarray(o_fused), np.asarray(o_unf), rtol=1e-5, atol=1e-5)

    def decoded(pk):
        w = ops.packed_operand(pk)
        return ref.decode_ref(w.idx_packed, w.sel_packed, w.inv_scale, cb, bcfg)

    expect = jnp.einsum("eck,enk->ecn", bcq.fake_quant(xe, cb, bcfg), jax.vmap(decoded)(packed))
    np.testing.assert_allclose(np.asarray(o_unf), np.asarray(expect), rtol=1e-5, atol=1e-5)
