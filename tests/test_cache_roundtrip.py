"""cache_init / cache_write / cache_read round-trips for every cache kind,
plus the bounded-prefix (valid_len) read and the page-pool layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcq
from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.models import layers

CFG = BCQConfig()
CB = default_universal_codebooks(CFG).as_jnp()
B, S, H, D = 2, 16, 2, 32
KINDS = ("bf16", "int8", "bcq4")


def _filled_cache(kind, key=0, n_prompt=5):
    k = jax.random.normal(jax.random.PRNGKey(key), (B, n_prompt, H, D))
    v = jax.random.normal(jax.random.PRNGKey(key + 1), (B, n_prompt, H, D))
    cache = layers.cache_init(B, S, H, D, kind, CFG)
    cache = layers.cache_write(cache, k, v, 0, kind, CFG, CB)
    return cache, k, v


@pytest.mark.parametrize("kind", KINDS)
def test_write_read_roundtrip(kind):
    """Written prefix dequantizes close to the source; quant error is
    bounded by the format's step size."""
    cache, k, v = _filled_cache(kind)
    kf, vf = layers.cache_read(cache, kind, CFG, CB, jnp.float32)
    assert kf.shape == (B, S, H, D)
    n = k.shape[1]
    tol = {"bf16": 1e-2, "int8": 2e-2, "bcq4": 0.2}[kind]
    for got, ref in ((kf[:, :n], k), (vf[:, :n], v)):
        err = jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))
        assert float(err) < tol, (kind, float(err))


@pytest.mark.parametrize("kind", KINDS)
def test_unwritten_positions_decode_to_zero(kind):
    cache, k, _ = _filled_cache(kind)
    kf, vf = layers.cache_read(cache, kind, CFG, CB, jnp.float32)
    n = k.shape[1]
    assert float(jnp.max(jnp.abs(kf[:, n:]))) == 0.0
    assert float(jnp.max(jnp.abs(vf[:, n:]))) == 0.0


@pytest.mark.parametrize("kind", KINDS)
def test_decode_append_matches_bulk_write(kind):
    """Token-at-a-time writes produce bit-identical cache reads to one bulk
    write (the paged/contiguous equivalence precondition)."""
    k = jax.random.normal(jax.random.PRNGKey(2), (B, 4, H, D))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, 4, H, D))
    bulk = layers.cache_write(layers.cache_init(B, S, H, D, kind, CFG), k, v, 0, kind, CFG, CB)
    step = layers.cache_init(B, S, H, D, kind, CFG)
    for t in range(4):
        step = layers.cache_write(step, k[:, t : t + 1], v[:, t : t + 1], t, kind, CFG, CB)
    kb, vb = layers.cache_read(bulk, kind, CFG, CB, jnp.float32)
    ks, vs = layers.cache_read(step, kind, CFG, CB, jnp.float32)
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(ks))
    np.testing.assert_array_equal(np.asarray(vb), np.asarray(vs))


@pytest.mark.parametrize("kind", KINDS)
def test_valid_len_bounds_the_read(kind):
    """cache_read(valid_len=n) equals the full read sliced to n — the
    dequant then never touches unwritten positions."""
    cache, _, _ = _filled_cache(kind)
    kf, vf = layers.cache_read(cache, kind, CFG, CB, jnp.float32)
    kb, vb = layers.cache_read(cache, kind, CFG, CB, jnp.float32, valid_len=8)
    assert kb.shape == (B, 8, H, D)
    np.testing.assert_array_equal(np.asarray(kf[:, :8]), np.asarray(kb))
    np.testing.assert_array_equal(np.asarray(vf[:, :8]), np.asarray(vb))


@pytest.mark.parametrize("kind", KINDS)
def test_paged_pool_gather_matches_contiguous(kind):
    """Scattering tokens into pages + block-table gather reproduces the
    contiguous cache read exactly."""
    ps, n_pages = 8, 4
    cache, k, v = _filled_cache(kind, n_prompt=S)  # fill all 16 positions
    pool = layers.cache_init(n_pages, ps, H, D, kind, CFG)
    # one sequence spanning pages 1 and 2, written one token at a time
    bt = jnp.asarray([[1, 2]], jnp.int32)
    kq, vq = k[:1], v[:1]
    for t in range(S):
        page_ids = bt[jnp.arange(1), jnp.asarray([t]) // ps]
        pool = layers.paged_token_write(
            pool, kq[:, t : t + 1], vq[:, t : t + 1], page_ids,
            jnp.asarray([t % ps]), kind, CFG, CB,
        )
    kg, vg = layers.paged_gather_kv(pool, bt, kind, CFG, CB, jnp.float32)
    kc, vc = layers.cache_read(cache, kind, CFG, CB, jnp.float32)
    np.testing.assert_array_equal(np.asarray(kg[0]), np.asarray(kc[0]))
    np.testing.assert_array_equal(np.asarray(vg[0]), np.asarray(vc[0]))


# ------------------------------------------- the bcq4 K/V encode, bit for bit
KV_SHAPES = ((1, 64, 12, 64), (2, 64, 12, 64), (128, 1, 12, 64))


def _np_e4m3(v):
    """Round positive float32 ``v`` to the E4M3 grid (RNE, saturating at
    448), from its exact binary exponent rather than a log2."""
    _, e = np.frexp(v)
    ulp = np.exp2(np.maximum(e - 1, -6) - 3).astype(np.float32)
    return np.minimum(np.round(v / ulp) * ulp, np.float32(448.0)).astype(np.float32)


def _np_e4m3_bits(g):
    _, e = np.frexp(g)
    ex = e - 1
    sub = g < 2.0**-6
    man = np.where(sub, g / 2.0**-9, (g / np.exp2(ex.astype(np.float32)) - 1.0) * 8)
    return (np.where(sub, 0, ex + 7) * 8 + np.round(man)).astype(np.uint8)


def _np_pack(v):
    v = v.astype(np.uint8)
    return (v[..., 1::2] << 4) | v[..., 0::2]


def _np_encode(x, levels, s_x=None):
    """NumPy LO-BCQ encode: per-array E4M3 scales, ``searchsorted`` per
    codebook, a table lookup, block errors summed left to right, the
    first codebook of least error, strided nibble packing."""
    cmax = np.float32(CFG.codeword_max)
    if s_x is None:
        amax = np.abs(x).max()
        s_x = cmax / amax if amax > 0 else np.float32(1.0)
    s_x = np.float32(s_x)
    lead = x.shape[:-1]
    arrays = x.reshape(*lead, -1, CFG.array_len)
    amax = np.abs(arrays).max(-1)
    s_a = np.where(amax > 0, cmax / np.where(amax > 0, amax, 1), s_x).astype(np.float32)
    ratio = np.maximum(_np_e4m3((s_a / s_x).astype(np.float32)), np.float32(2.0**-9))
    y = (arrays * (ratio * s_x)[..., None]).astype(np.float32)
    blocks = y.reshape(*lead, -1, CFG.blocks_per_array, CFG.block_len)
    best = sel = idx = None
    for c, lv in enumerate(levels):
        cand = np.searchsorted(0.5 * (lv[1:] + lv[:-1]), blocks, side="right")
        sq = (blocks - lv[cand]) ** 2
        err = sq[..., 0]
        for j in range(1, CFG.block_len):
            err = err + sq[..., j]
        if best is None:
            best, sel, idx = err, np.zeros(err.shape, np.int64), cand
            continue
        take = err < best
        best = np.where(take, err, best)
        sel = np.where(take, c, sel)
        idx = np.where(take[..., None], cand, idx)
    return (_np_pack(idx.reshape(*lead, -1)), _np_pack(sel.reshape(*lead, -1)),
            _np_e4m3_bits(ratio))


def _kv_input(shape, kind):
    rng = np.random.default_rng(list(KV_SHAPES).index(shape))
    if kind == "all_zero":
        return np.zeros(shape, np.float32), None
    if kind == "midpoints":
        # Every array holds ±31, so with s_X = 1 its scale is exactly 1 and
        # the values reach the codebooks unchanged: each midpoint of every
        # codebook, and each level, lands on a scalar.
        levels = np.asarray(CB)
        pts = np.concatenate([(0.5 * (levels[:, 1:] + levels[:, :-1])).ravel(),
                              levels.ravel()])
        x = rng.choice(pts, size=shape).astype(np.float32)
        x[..., 0] = np.where(rng.random(shape[:-1]) < 0.5, 31.0, -31.0)
        return x, np.float32(1.0)
    x = (rng.standard_normal(shape) * 2.5).astype(np.float32)
    x[rng.random(shape) < 0.01] *= 8.0
    if kind == "zeros":
        x[rng.random(shape) < 0.5] = 0.0
        x[rng.random(shape[:-1]) < 0.1] = 0.0  # whole all-zero arrays too
    return x, None


@pytest.mark.parametrize("kind", ("normal", "midpoints", "zeros", "all_zero"))
@pytest.mark.parametrize("shape", KV_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_bcq_encode_matches_numpy_oracle(shape, kind):
    """bcq.encode of a K/V block gives the bytes of a NumPy oracle built
    from ``searchsorted``, a table lookup and a first-wins argmin."""
    x, s_x = _kv_input(shape, kind)
    want_idx, want_sel, want_scale = _np_encode(x, np.asarray(CB), s_x)
    enc = bcq.encode(jnp.asarray(x), CB, CFG,
                     s_x=None if s_x is None else jnp.float32(s_x))
    np.testing.assert_array_equal(np.asarray(enc.scale_code), want_scale)
    np.testing.assert_array_equal(np.asarray(enc.packed_sel), want_sel)
    np.testing.assert_array_equal(np.asarray(enc.packed_idx), want_idx)


@pytest.mark.parametrize("shape", (KV_SHAPES[0], KV_SHAPES[2]),
                         ids=lambda s: "x".join(map(str, s)))
def test_bcq_encode_lowers_without_gather_or_loop(shape):
    """The K/V page encode is elementwise compares and selects: a gather
    or a while loop in it runs value by value on the chip."""
    text = bcq.encode.lower(
        jax.ShapeDtypeStruct(shape, jnp.float32), CB, CFG, s_x=jnp.float32(1.0)
    ).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.while" not in text
