"""Pallas kernel validation: the fused W4A4 linear's pieces and the
kernel itself (interpret mode) vs the pure-jnp oracles of kernels/ref.py.

Contract: the *decoded values* (and therefore every downstream GEMM) must be
bit-identical between the kernel's in-VMEM encode and kernels/ref.py.  Raw
selector / index bytes may legitimately differ when a block ties between two
codebooks (or a codebook holds duplicate INT6 entries) — tests check value
equality.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcq
from repro.core.bcq import BCQConfig
from repro.kernels import ops, ref
from repro.kernels.common import encode_tile

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


@partial(jax.jit, static_argnames=("cfg", "tile_m", "tile_k"))
def _encode_tiles(x, cb, s_x, cfg, tile_m, tile_k):
    """``common.encode_tile`` over an (M, K) operand in (tile_k, tile_m)
    K-major tiles, as the fused kernel walks its activation slab;
    returns row-major idx (M, K), sel (M, K/L_b), ratio (M, K/L_A) and
    the chosen codewords (M, K)."""
    m, k = x.shape
    tiles = x.reshape(m // tile_m, tile_m, k // tile_k, tile_k).transpose(0, 2, 3, 1)
    outs = jax.vmap(jax.vmap(lambda t: encode_tile(t, cb, s_x, cfg)))(tiles)
    return [a.transpose(0, 3, 1, 2).reshape(m, -1) for a in outs]


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["normal", "heavy", "outlier"])
def test_quantize_kernel_matches_ref(cfg, dtype, kind):
    """The fused linear's in-VMEM encode (``common.encode_tile``, on a
    64 × 256 tile grid) against ``ref.quantize_ref``: equal scales, and
    decoded values equal up to near-ties between codebooks."""
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(7), (128, 512), dtype, kind).astype(jnp.float32)
    s_x = bcq.tensor_scale(x, cfg)
    idx, sel, rt, q = _encode_tiles(x, cb, s_x, cfg, tile_m=64, tile_k=256)
    ip, sp = bcq.pack_nibbles(idx), bcq.pack_nibbles(sel)
    ip2, sp2, rt2 = ref.quantize_ref(x, cb, cfg, s_x)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(rt2))
    inv = 1.0 / (rt * s_x)
    d1 = ref.decode_ref(ip, sp, inv, cb, cfg)
    d2 = ref.decode_ref(ip2, sp2, inv, cb, cfg)
    # the encode's chosen codewords are the decode of its own codes
    np.testing.assert_array_equal(
        np.asarray(q * jnp.repeat(inv, cfg.array_len, axis=-1)), np.asarray(d1)
    )
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
    """The fused kernel against its oracle ``ref.fused_linear_ref``."""
    m, n, k = mnk
    cb = _codebooks(cfg)
    a = _dists(jax.random.PRNGKey(1), (m, k), jnp.float32, "normal")
    w = _dists(jax.random.PRNGKey(2), (n, k), jnp.float32, "heavy")
    pw = ops.quantize(w, cb, cfg)
    o_ref = ops.w4a4_linear_fused(a, pw, cb, cfg, impl="ref")
    o_pl = ops.w4a4_linear_fused(
        a, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256
    )
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("cfg", CFGS[:2], ids=lambda c: c.tag())
def test_matmul_matches_fake_quant_path(cfg):
    """Fused W4A4 kernel == the fake_full emulation (both operands
    quantize-dequantized, then one f32 GEMM)."""
    cb = _codebooks(cfg)
    a = _dists(jax.random.PRNGKey(3), (96, 512), jnp.float32, "outlier")
    w = _dists(jax.random.PRNGKey(4), (160, 512), jnp.float32, "normal")
    pw = ops.quantize(w, cb, cfg)
    out = ops.w4a4_linear_fused(
        a, pw, cb, cfg, impl="pallas", tile_m=32, tile_n=32, tile_k=256
    )
    expect = bcq.fake_quant(a, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_quantize_wrapper_pads_ragged_shapes():
    """Rows, columns and K none tile-aligned (K still a multiple of L_A):
    the fused linear's wrapper pads K to its tile with whole arrays whose
    weight dequant scales are zero, so the padding adds nothing."""
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(5), (100, 320), jnp.float32, "normal")
    w = _dists(jax.random.PRNGKey(6), (70, 320), jnp.float32, "normal")
    pw = ops.quantize(w, cb, cfg)
    out = ops.w4a4_linear_fused(
        x, pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=256
    )
    expect = bcq.fake_quant(x, cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    assert out.shape == (100, 70)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-3)


def test_w4a4_linear_nd_input():
    cfg = BCQConfig()
    cb = _codebooks(cfg)
    x = _dists(jax.random.PRNGKey(8), (2, 16, 256), jnp.bfloat16, "normal")
    w = _dists(jax.random.PRNGKey(9), (128, 256), jnp.float32, "normal")
    pw = ops.quantize(w, cb, cfg)
    out = ops.w4a4_linear_fused(x, pw, cb, cfg, impl="pallas", tile_m=32, tile_n=64)
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
    p = ops.quantize(w, cb, cfg)
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
    p = ops.quantize(x, cb, cfg)
    bits = (p.idx_packed.size + p.sel_packed.size) * 8 + p.inv_scale.size * 8
    assert bits / x.size == pytest.approx(cfg.bitwidth(), abs=1e-9)
