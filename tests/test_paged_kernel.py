"""Pallas paged-attention decode kernel == pure-JAX oracle (interpret mode),
for all three page kinds, GQA replication, and ragged sequence lengths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.kernels import ref as kref
from repro.kernels.common import page_gather_attention, page_schedule
from repro.kernels.paged_attention import paged_attention
from repro.models import layers

CFG = BCQConfig()
CB = default_universal_codebooks(CFG).as_jnp()
P, PS, HKV, D = 6, 8, 2, 32


def _pool(kind, key=0):
    pool = layers.cache_init(P, PS, HKV, D, kind, CFG)
    k = jax.random.normal(jax.random.PRNGKey(key), (P, PS, HKV, D))
    v = jax.random.normal(jax.random.PRNGKey(key + 1), (P, PS, HKV, D))
    return layers.cache_write(pool, k, v, 0, kind, CFG, CB)


@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
@pytest.mark.parametrize("h", (2, 4))  # MHA and 2× GQA replication
def test_kernel_matches_reference(kind, h):
    pool = _pool(kind)
    rng = np.random.default_rng(0)
    b, maxp = 3, 3
    bt = jnp.asarray(rng.integers(0, P, (b, maxp)), jnp.int32)
    lengths = jnp.asarray([1, 17, 24], jnp.int32)  # partial / mid / full
    q = jax.random.normal(jax.random.PRNGKey(7), (b, h, D))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, kind, CFG, CB)
    got = paged_attention(q, pool, bt, lengths, kind, CFG, CB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kernel_reads_only_referenced_pages():
    """Pages outside the block table cannot affect the output (the whole
    point of paged reads): corrupt an unreferenced page, output unchanged."""
    pool = _pool("bf16")
    bt = jnp.asarray([[1, 2, 3]], jnp.int32)
    lengths = jnp.asarray([20], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, HKV, D))
    out1 = paged_attention(q, pool, bt, lengths, "bf16", CFG, interpret=True)
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[5].set(1e6)
    pool2["v"] = pool["v"].at[5].set(1e6)
    out2 = paged_attention(q, pool2, bt, lengths, "bf16", CFG, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_kernel_masks_beyond_length():
    """Tokens past lengths[b] in the tail page are invisible."""
    pool = _pool("bf16")
    bt = jnp.asarray([[1, 2, 0]], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (1, HKV, D))
    out_a = paged_attention(q, pool, bt, jnp.asarray([9], jnp.int32), "bf16", CFG, interpret=True)
    # corrupt positions >= 9 of page 2 (offsets 1..) — must not change out
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[2, 1:].set(777.0)
    pool2["v"] = pool["v"].at[2, 1:].set(777.0)
    out_b = paged_attention(q, pool2, bt, jnp.asarray([9], jnp.int32), "bf16", CFG, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


# ------------------------------------------------------------- boundaries
@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
def test_length_exactly_at_page_boundary(kind):
    """lengths == maxp·page_size: every token of every page is live and the
    final page's mask admits its last token (off-by-one hotspot)."""
    pool = _pool(kind)
    maxp = 3
    bt = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lengths = jnp.asarray([maxp * PS, 2 * PS], jnp.int32)  # full table / full pages
    q = jax.random.normal(jax.random.PRNGKey(5), (2, HKV, D))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, kind, CFG, CB)
    got = paged_attention(q, pool, bt, lengths, kind, CFG, CB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_single_token_sequences_every_page_slot():
    """length-1 sequences, one per distinct pool page: only (page, offset 0)
    is visible, wherever the page lives in the pool."""
    pool = _pool("bf16")
    b = P - 1  # one sequence per real page
    bt = jnp.stack([jnp.asarray([p, 0, 0], jnp.int32) for p in range(1, P)])
    lengths = jnp.ones((b,), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(6), (b, HKV, D))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, "bf16", CFG, CB)
    got = paged_attention(q, pool, bt, lengths, "bf16", CFG, CB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)
    # a length-1 output is attention over exactly one token: v itself
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(pool["v"][1:, 0].astype(jnp.float32)),
        atol=2e-5, rtol=2e-5,
    )


def test_null_padded_table_beyond_tail():
    """Block tables padded entirely with NULL_PAGE beyond the tail: the
    null page's contents (scratch target for idle slots) must be invisible,
    however long the padding."""
    pool = _pool("bf16")
    bt = jnp.asarray([[3, 0, 0, 0, 0, 0]], jnp.int32)  # 1 live page, 5 null
    lengths = jnp.asarray([5], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(8), (1, HKV, D))
    out_a = paged_attention(q, pool, bt, lengths, "bf16", CFG, interpret=True)
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[0].set(1e6)  # poison the null page
    pool2["v"] = pool["v"].at[0].set(-1e6)
    out_b = paged_attention(q, pool2, bt, lengths, "bf16", CFG, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, "bf16", CFG, CB)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------------- live-page grid coverage
@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
def test_ragged_lengths_with_zero_length_padding_slots(kind):
    """A batch mixing ragged live lengths with ZERO-length padding slots
    (all-NULL tables — what the engine passes for inactive decode rows):
    the live-page grid gives every row at least one step, so padded rows
    produce the same defined output as the oracle and live rows are
    unaffected by their neighbours."""
    pool = _pool(kind)
    bt = jnp.asarray(
        [[1, 2, 3], [0, 0, 0], [4, 0, 0], [0, 0, 0]], jnp.int32
    )
    lengths = jnp.asarray([19, 0, 3, 0], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(11), (4, HKV, D))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, kind, CFG, CB)
    got = paged_attention(q, pool, bt, lengths, kind, CFG, CB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_null_heavy_tables_skip_null_page_reads():
    """NULL-heavy block tables: the live-page schedule visits only
    ceil(len/ps) pages per row, so poisoning the null page cannot leak into
    any live row no matter how much of the table is padding."""
    pool = _pool("bf16")
    bt = jnp.asarray([[3, 0, 0, 0, 0, 0], [5, 2, 0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([6, 11], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(12), (2, HKV, D))
    out_a = paged_attention(q, pool, bt, lengths, "bf16", CFG, interpret=True)
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[0].set(3e4)
    pool2["v"] = pool["v"].at[0].set(-3e4)
    out_b = paged_attention(q, pool2, bt, lengths, "bf16", CFG, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    ref = kref.paged_attention_ref(q, pool, bt, lengths, "bf16", CFG, CB)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_mxu_onehot_page_dequant_bitwise_exact():
    """The in-kernel bcq4 page dequant (lane-repeat nibble unpack +
    lane-gather codeword lookup, ``common.dequant_page``) is bit-exact
    against the reference flat-gather dequant of the same page bytes."""
    from repro.kernels.common import codebook_lookup, dequant_page, flat_codebook

    pool = _pool("bcq4")
    page = 3
    refs = [pool[n][page : page + 1] for n in ("k_idx", "k_sel", "k_scale")]
    got = dequant_page("bcq4", refs, CFG, flat_codebook(CB), pool["k_sx"])
    want = kref._dequant_pool_ref(dict(pool, _cb=CB), "k", "bcq4", CFG)[page]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want.swapaxes(0, 1)))
    # every combined codeword of every codebook decodes to its table entry
    rng = np.random.default_rng(0)
    code = jnp.asarray(
        rng.integers(0, CFG.n_codebooks * CFG.n_entries, size=(PS * HKV, 3 * D)),
        jnp.int32,
    )
    np.testing.assert_array_equal(
        np.asarray(codebook_lookup(code, flat_codebook(CB))),
        np.asarray(CB.astype(jnp.float32).reshape(-1)[code]),
    )


def test_model_paged_gather_matches_kernel():
    """The model's jnp gather+dequant decode path and the Pallas kernel
    agree on the same pool/table state (bcq4, GQA)."""
    pool = _pool("bcq4")
    bt = jnp.asarray([[4, 1, 2], [3, 0, 0]], jnp.int32)
    lengths = jnp.asarray([19, 6], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 4, D))
    kf, vf = layers.paged_gather_kv(pool, bt, "bcq4", CFG, CB, jnp.float32)
    s = jnp.einsum("bhd,bthd->bht", q, jnp.repeat(kf, 2, 2)) * (D**-0.5)
    mask = jnp.arange(kf.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
    ref = jnp.einsum("bht,bthd->bhd", p, jnp.repeat(vf, 2, 2))
    got = paged_attention(q, pool, bt, lengths, "bcq4", CFG, CB, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------------ the schedule and its grid
def _schedule_oracle(kv_len, ps, maxp):
    """The schedule by ``np.searchsorted``: flat step t serves the row
    whose [start, end) holds min(t, total - 1)."""
    counts = np.clip(-(-kv_len // ps), 1, maxp)
    starts = np.concatenate([[0], np.cumsum(counts)])
    total = starts[-1]
    t = np.arange(len(kv_len) * maxp)
    t_eff = np.minimum(t, total - 1)
    sid = np.searchsorted(starts[1:], t_eff, side="right")
    pin = t_eff - starts[sid]
    live = t < total
    return sid, pin, (pin == 0) & live, (pin == counts[sid] - 1) & live, live, total


@pytest.mark.parametrize(
    "kv_len",
    [
        [0] * 8,  # every row idle: one step each
        [4 * PS] + [0] * 7,  # one row at MAXP, the rest idle
        [3, 17, 0, 9, 24, 1, 0, 16],  # ragged
        [9 * PS, 4 * PS + 1, 0, 100 * PS],  # past MAXP·ps: clipped
        [13],  # a single row
    ],
    ids=["all-idle", "one-full-row", "ragged", "clipped", "one-row"],
)
def test_page_schedule_matches_searchsorted_oracle(kv_len):
    maxp = 4
    kv = np.asarray(kv_len, np.int32)
    got = jax.jit(page_schedule, static_argnums=(1, 2))(jnp.asarray(kv), PS, maxp)
    want = _schedule_oracle(kv.astype(np.int64), PS, maxp)
    for name, g, w in zip(("sid", "pin", "first", "last", "live", "total"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w, np.int32), err_msg=name)


@pytest.mark.parametrize("kind", ("bf16", "bcq4"))
def test_decode_launch_of_mostly_idle_rows(kind):
    """The serving decode shape: many rows idle (length 0, all-NULL
    tables) beside a few multi-page rows.  The grid runs only the live
    steps, one per idle row, yet every row's output is written and equals
    the oracle."""
    pool = _pool(kind)
    b, maxp = 16, 4
    rng = np.random.default_rng(3)
    bt = np.zeros((b, maxp), np.int32)
    lengths = np.zeros((b,), np.int32)
    for r, n in ((2, 4 * PS), (9, 2 * PS + 3), (13, 5)):
        lengths[r] = n
        bt[r] = rng.integers(1, P, maxp)
    q = jax.random.normal(jax.random.PRNGKey(13), (b, 4, D))
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    ref = kref.paged_attention_ref(q, pool, bt, lengths, kind, CFG, CB)
    got = paged_attention(q, pool, bt, lengths, kind, CFG, CB, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_page_gather_grid_is_bounded_by_the_live_total():
    """The lowered ``pallas_call`` has one grid axis, bounded at run time
    (one dynamic grid bound), not the static B·MAXP."""
    pool = _pool("bcq4")
    q = jnp.zeros((16, 1, 4, D), jnp.float32)
    bt = jnp.zeros((16, 4), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q, pool, bt, kv: page_gather_attention(q, pool, bt, kv, "bcq4", CFG, CB, True)
    )(q, pool, bt, jnp.zeros((16,), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    gm = calls[0].params["grid_mapping"]
    assert gm.num_dynamic_grid_bounds == 1 and len(gm.grid) == 1
