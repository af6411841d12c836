"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Contract: the *decoded values* (and therefore every downstream GEMM) must be
bit-identical between the Pallas kernels and kernels/ref.py.  Raw selector /
index bytes may legitimately differ when a block ties between two codebooks
(or a codebook holds duplicate INT6 entries) — tests check value equality.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcq
from repro.core.bcq import BCQConfig
from repro.kernels import ops, ref
from repro.kernels.bcq_matmul import bcq_matmul_pallas
from repro.kernels.bcq_quantize import bcq_quantize_pallas

CFGS = [
    BCQConfig(),  # paper default g64 / L_b 8 / N_c 8
    BCQConfig(block_len=8, array_len=128, n_codebooks=16),
    BCQConfig(block_len=4, array_len=32, n_codebooks=4),
    BCQConfig(block_len=2, array_len=16, n_codebooks=2),
]


def _codebooks(cfg, seed=0):
    data = jax.random.laplace(jax.random.PRNGKey(seed), (60000,))
    return bcq.fit_lobcq(data, cfg, iters=4, max_blocks=4096).as_jnp()


def _dists(key, shape, dtype, kind):
    if kind == "normal":
        x = jax.random.normal(key, shape)
    elif kind == "heavy":
        x = jax.random.t(key, 3.0, shape)
    elif kind == "outlier":
        x = jax.random.normal(key, shape)
        mask = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.005, shape)
        x = jnp.where(mask, x * 40.0, x)
    else:
        x = jax.random.uniform(key, shape, minval=-3, maxval=3)
    return x.astype(dtype)


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["normal", "heavy", "outlier"])
def test_quantize_kernel_matches_ref(cfg, dtype, kind):
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(7), (128, 512), dtype, kind)
    s_x = bcq.tensor_scale(x.astype(jnp.float32), cfg)
    ip, sp, rt = bcq_quantize_pallas(
        x.astype(jnp.float32), cb, s_x, cfg, tile_m=64, tile_k=256, interpret=True
    )
    ip2, sp2, rt2 = ref.quantize_ref(x.astype(jnp.float32), cb, cfg, s_x)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(rt2))
    inv = 1.0 / (rt * s_x)
    d1 = ref.decode_ref(ip, sp, inv, cb, cfg)
    d2 = ref.decode_ref(ip2, sp2, inv, cb, cfg)
    # Decoded values must agree except where a block ties between two
    # codebooks at *identical* MSE — so compare per-block quantization error.
    xf = np.asarray(x, np.float32)
    e1 = ((np.asarray(d1) - xf) ** 2).reshape(-1, cfg.block_len).sum(-1)
    e2 = ((np.asarray(d2) - xf) ** 2).reshape(-1, cfg.block_len).sum(-1)
    np.testing.assert_allclose(e1, e2, rtol=1e-4, atol=1e-7)
    mismatch = (np.asarray(d1) != np.asarray(d2)).mean()
    assert mismatch < 1e-3  # ties are rare


@pytest.mark.parametrize("cfg", CFGS[:2], ids=lambda c: c.tag())
@pytest.mark.parametrize(
    "mnk", [(128, 128, 512), (64, 192, 1024), (256, 128, 512)]
)
def test_matmul_kernel_matches_ref(cfg, mnk):
    m, n, k = mnk
    cb = _codebooks(cfg)
    a = _dists(jax.random.PRNGKey(1), (m, k), jnp.float32, "normal")
    w = _dists(jax.random.PRNGKey(2), (n, k), jnp.float32, "heavy")
    pa = ops.quantize(a, cb, cfg, impl="ref")
    pw = ops.quantize(w, cb, cfg, impl="ref")
    o_ref = ops.matmul(pa, pw, cb, cfg, impl="ref")
    o_pl = ops.matmul(pa, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("cfg", CFGS[:2], ids=lambda c: c.tag())
def test_matmul_matches_fake_quant_path(cfg):
    """Packed W4A4 GEMM == fake-quant (quantize-dequantize bf16) GEMM."""
    cb = _codebooks(cfg)
    a = _dists(jax.random.PRNGKey(3), (96, 512), jnp.float32, "outlier")
    w = _dists(jax.random.PRNGKey(4), (160, 512), jnp.float32, "normal")
    pa = ops.quantize(a, cb, cfg, impl="pallas")
    pw = ops.quantize(w, cb, cfg, impl="pallas")
    out = ops.matmul(pa, pw, cb, cfg, impl="pallas", tile_m=32, tile_n=32, tile_k=256)
    expect = bcq.fake_quant(a, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_quantize_wrapper_pads_ragged_shapes():
    # rows and K not tile-aligned (K must still be a multiple of L_A)
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(5), (100, 320), jnp.float32, "normal")
    w = _dists(jax.random.PRNGKey(6), (70, 320), jnp.float32, "normal")
    pa = ops.quantize(x, cb, cfg, impl="pallas", tile_m=64, tile_k=256)
    pw = ops.quantize(w, cb, cfg, impl="pallas", tile_m=64, tile_k=256)
    out = ops.matmul(pa, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256)
    expect = bcq.fake_quant(x, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    assert out.shape == (100, 70)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_w4a4_linear_nd_input():
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(8), (2, 16, 256), jnp.bfloat16, "normal")
    w = _dists(jax.random.PRNGKey(9), (128, 256), jnp.float32, "normal")
    pw = ops.quantize(w, cb, cfg, impl="ref")
    out = ops.w4a4_linear(x, pw, cb, cfg, impl="ref")
    assert out.shape == (2, 16, 128) and out.dtype == jnp.bfloat16
    expect = bcq.fake_quant(x.astype(jnp.float32).reshape(-1, 256), cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    np.testing.assert_allclose(
        np.asarray(out.reshape(-1, 128).astype(jnp.float32)), np.asarray(expect), rtol=0.02, atol=0.05
    )


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
def test_decode_rows_matches_ref_decode(cfg):
    """The in-kernel row decode (``common.decode_rows``: bytes spread over
    their scalars by lane gathers, nibble by lane parity, codeword looked
    up from the flattened codebook) equals ``ref.decode_ref`` bit for bit —
    at a width that is not a whole number of 128-lane chunks, and for
    codebook tables of 32 to 256 entries."""
    from repro.kernels.common import decode_rows, flat_codebook

    cb = _codebooks(cfg)
    w = _dists(jax.random.PRNGKey(10), (24, 5 * cfg.array_len), jnp.float32, "heavy")
    p = ops.quantize(w, cb, cfg, impl="ref")
    got = decode_rows(
        p.idx_packed.astype(jnp.int32), p.sel_packed.astype(jnp.int32),
        p.inv_scale, flat_codebook(cb), cfg, w.shape[1],
    )
    want = ref.decode_ref(p.idx_packed, p.sel_packed, p.inv_scale, cb, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_e4m3_snap_matches_format_quantize():
    """The kernel-side E4M3 snap (binade from the exponent bits, scaling by
    exact powers of two) equals ``formats.E4M3.quantize`` bit for bit on
    every grid point, every midpoint (ties to even) and a log-uniform
    sweep past both ends of the range; values below the smallest
    subnormal snap up to it, as the encode requires a non-zero scale."""
    from repro.core import formats
    from repro.kernels.common import e4m3_snap

    grid = formats.E4M3.levels()[1:]
    sweep = 2.0 ** np.random.default_rng(0).uniform(-14.0, 10.0, 4096)
    a = jnp.asarray(
        np.concatenate([grid, (grid[:-1] + grid[1:]) / 2, sweep]), jnp.float32
    )
    want = jnp.maximum(formats.E4M3.quantize(a), formats.E4M3.min_subnormal)
    np.testing.assert_array_equal(np.asarray(e4m3_snap(a)), np.asarray(want))


def test_packed_storage_bit_accounting():
    """Packed buffers realize Eq. 9's bit budget exactly (excl. codebooks).

    Storage packs selectors at nibble granularity, so the budget is exact
    for N_c = 16 (4-bit selectors); smaller N_c pays ≤1 bit/block of
    alignment padding (noted in DESIGN.md).
    """
    cfg = BCQConfig(n_codebooks=16)  # 4 + 4/8 + 8/64 = 4.625 bits
    cb = _codebooks(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 1024))
    p = ops.quantize(x, cb, cfg, impl="ref")
    bits = (p.idx_packed.size + p.sel_packed.size) * 8 + p.inv_scale.size * 8
    assert bits / x.size == pytest.approx(cfg.bitwidth(), abs=1e-9)
