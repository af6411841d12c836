"""Shared helpers for the LO-BCQ Pallas kernels.

The pieces the fused W4A4 linear and the paged attention kernels share
(their jnp oracles are in ``ref.py``):

* the kernel-safe E4M3 round-to-nearest (``e4m3_snap``),
* backend-aware ``interpret`` resolution (``resolve_interpret``),
* the threshold-compare LO-BCQ encode of one VMEM tile (``encode_tile``),
  which the fused linear kernel runs on each activation tile (its
  oracle is ``ref.quantize_ref``; ``core/bcq.encode`` is its XLA twin),
* the packed-row decode ``decode_rows`` (weight row tiles of the fused
  linear and bcq4 pages alike), built on the codeword lookup
  ``codebook_lookup`` and the metadata expansion ``expand_lanes``:
  per-128-lane-chunk ``take_along_axis`` lane gathers (one
  ``tpu.dynamic_gather`` per vreg on the chip) — see bcq_linear.py DESIGN,
* the **page-gather attention core** (``page_gather_attention``) shared by
  the paged decode kernel (kernels/paged_attention.py) and the chunked
  prefill kernel (kernels/chunked_prefill.py) — DESIGN below.

TPU LAYOUT RULES THESE HELPERS FOLLOW
=====================================

Mosaic tiles a vector's last two dims as (8 sublanes, 128 lanes).  It
lowers reshapes that split or merge the *sublane* or leading dims, lane
concatenation, 2-D transposes and batched matmuls with a leading batch
dim; it refuses a reshape that splits the lane dim (``(T, 512) →
(T, 8, 64)``) and the stack-and-reshape nibble interleave.  So the encode
runs K-major (the reduction axis on sublanes: per-array / per-block
reductions are sublane reductions), per-scalar metadata is expanded by
lane gathers from 128-lane source chunks, and the codeword table lookup
is a lane gather from the ≤128-entry flattened codebook held in one vreg
row.  Every lookup is exact (a gather moves bits), so the chip and the
interpreter decode identical values.

PAGE-GATHER CORE DESIGN
=======================

One kernel serves both paged attention shapes: decode is the C == 1 case of
a chunk (a decode query at position ``len-1`` sees exactly the tokens a
chunk query at ``qpos = kv_len - C + c`` does under the single mask
``tpos <= qpos``).  Three hot-path properties:

1. **Live-page-only grid.**  The core runs a FLAT grid over a
   scalar-prefetched *schedule*: per sequence ``clip(ceil(kv_len/ps), 1,
   MAXP)`` steps (``page_counts``; min 1, so every output row is
   written), concatenated.  The grid's length is the live total,
   ``Σ page_counts``, a bound known only at run time (one dynamic grid
   bound), so no step runs past the last live page.  A static grid of
   ``B·MAXP`` steps, with the dead tail replaying the last live block
   index, moved no bytes there either, but paid a pipeline step for
   each: at a 128-row decode launch with ~2 rows live that was ~16 k
   steps a layer for ~200 of work.  HBM traffic is exactly the live
   pages (NULL table padding is never visited).  Schedule arrays
   (``sid``/``pin``/``first``/``last``/``live``, one int32 per step of
   the ``B·MAXP`` bound) ride in scalar memory via
   ``PrefetchScalarGridSpec``; they are built by compares and sums over
   rows (``page_schedule``), with no gather or loop.

2. **Lane-gather dequant for bcq4 pages.**  The page's (head, token)
   vectors are decoded as rows by ``decode_rows``: index and selector
   bytes spread over their scalars by lane gathers, the nibble picked by
   lane parity (no stack-and-reshape), and ``cb[sel·2^B + idx]`` looked
   up by a lane gather from the flattened codebook.  Gathers move bits,
   so the dequantized page is bit-identical to the reference gather.

3. **Head-major, repeat-free GQA.**  The wrapper lays q out as
   ``(B, Hkv, rep·C, D)``: each KV group's query rows sit on sublanes and
   the score / accumulate contractions are batched matmuls over the
   leading Hkv dim (the page is swapped from ``(ps, Hkv, D)`` to
   ``(Hkv, ps, D)`` in VMEM) — K/V are never repeated ``rep``×.

VMEM per step (f32): q block Hkv·R·D, one K + one V page (packed bytes by
kind; double-buffered: two of each), scratch m/l 2·Hkv·R + acc Hkv·R·D,
scores Hkv·R·ps (lane-padded to 128).  For serving shapes (C ≤ 64,
H ≤ 32, D ≤ 128, ps ≤ 64) that is a few MiB at most — inside the
~16 MiB envelope.

Shape-bucketing policy (serving layer, see serving/engine.py): chunk
length and prefill batch bucket to powers of two, block tables grow by
doubling — so steady-state serving stops retracing; the kernels here are
shape-polymorphic per bucket, not per request.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bcq import BCQConfig
from repro.core.formats import bits_to_e4m3_impl, pow2

NEG = -1e30

_E4M3_MAX = 448.0
_E4M3_MIN_SUB = 2.0**-9
LANES = 128


def resolve_interpret(interpret: bool | None) -> bool:
    """None → interpret off TPU, native on TPU (a direct TPU call can never
    silently run interpret mode); an explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def e4m3_snap(a: jax.Array) -> jax.Array:
    """Inline E4M3 round-to-nearest for positive values (kernel-safe ops).

    The binade exponent comes from the f32 exponent bits and every scaling
    is by an exact power of two, so the result is bit-identical on the
    chip, in the interpreter and in XLA (``formats.E4M3.quantize``)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    e = jnp.clip(((bits >> 23) & 0xFF) - 127, -6, 8)
    q = jnp.round(a * pow2(3 - e)) * pow2(e - 3)
    q = jnp.minimum(q, _E4M3_MAX)
    return jnp.maximum(q, _E4M3_MIN_SUB)


def encode_tile(xt: jax.Array, cb, s_x: jax.Array, cfg: BCQConfig):
    """LO-BCQ encode of one K-major (TK, TM) f32 tile resident in VMEM.

    Per block array: |max| reduce → ŝ_A = E4M3(s_A/s_X); per codebook
    (unrolled, N_c ≤ 16): per-scalar nearest sorted entry via 2^B−1
    threshold compares, block MSE, running argmin over codebooks.  All
    compare+select+FMA on the VPU — no gather.  K runs along sublanes, so
    the per-array and per-block reductions are sublane reductions.
    ``cb[i, t]`` must yield scalars (an SMEM ref or a concrete array).
    Its XLA twin is ``core/bcq.encode``, which writes the bcq4 K/V pages
    with the same compares and selects; it sums each block error left to
    right, this reduction in its own order, so a near-tie between two
    codebooks may go either way.

    Returns, K-major: idx (TK, TM) i32, sel (TK/L_b, TM) i32,
    ratio (TK/L_A, TM) f32 and the chosen codewords ``cb[sel, idx]``
    (TK, TM) f32.
    """
    tk, tm = xt.shape
    la, lb, nc, ne = cfg.array_len, cfg.block_len, cfg.n_codebooks, cfg.n_entries
    na, nb = tk // la, tk // lb

    arrays = xt.reshape(na, la, tm)
    amax = jnp.max(jnp.abs(arrays), axis=1)
    s_a = jnp.where(amax > 0, cfg.codeword_max / amax, s_x)
    ratio = e4m3_snap(s_a / s_x)
    y = arrays * (ratio * s_x)[:, None, :]
    blocks = y.reshape(nb, lb, tm)

    best_err = jnp.full((nb, tm), jnp.inf, jnp.float32)
    best_sel = jnp.zeros((nb, tm), jnp.int32)
    best_idx = jnp.zeros(blocks.shape, jnp.int32)
    best_q = jnp.zeros(blocks.shape, jnp.float32)
    for i in range(nc):  # unrolled: N_c ≤ 16
        lv = [cb[i, t] for t in range(ne)]
        idx = jnp.zeros(blocks.shape, jnp.int32)
        for t in range(ne - 1):  # nearest sorted entry via threshold compares
            idx += (blocks >= 0.5 * (lv[t] + lv[t + 1])).astype(jnp.int32)
        q = jnp.zeros(blocks.shape, jnp.float32)
        for t in range(ne):  # masked-sum decode (no gather on TPU)
            q += jnp.where(idx == t, lv[t], 0.0)
        err = jnp.sum((blocks - q) ** 2, axis=1)
        take = err < best_err
        best_err = jnp.where(take, err, best_err)
        best_sel = jnp.where(take, i, best_sel)
        best_idx = jnp.where(take[:, None, :], idx, best_idx)
        best_q = jnp.where(take[:, None, :], q, best_q)

    return best_idx.reshape(tk, tm), best_sel, ratio, best_q.reshape(tk, tm)


def _lane_gather(src: jax.Array, idx: jax.Array) -> jax.Array:
    """out[r, l] = src[r, idx[r, l]] for one (R, 128) vreg-wide chunk."""
    return jnp.take_along_axis(src, idx, axis=1, mode="promise_in_bounds")


def _pad_lanes(v: jax.Array, mult: int = LANES) -> jax.Array:
    pad = (-v.shape[-1]) % mult
    if not pad:
        return v
    return jnp.concatenate([v, jnp.zeros(v.shape[:-1] + (pad,), v.dtype)], axis=-1)


def expand_lanes(src, rep: int, chunk: int) -> jax.Array:
    """Lane ``l`` of 128-lane output chunk ``chunk`` ← ``src[:, (chunk·128
    + l) // rep]``: per-array scales (rep = L_A), per-block selector bytes
    (rep = 2·L_b) or index bytes (rep = 2) spread over the scalars they
    cover.  ``src`` (R, n) is an array, lane-padded to 128 here, or a
    VMEM ref, of which only that source chunk is loaded (integers widened
    to int32); the gather reads one 128-lane source chunk, so ``rep`` must
    divide 128."""
    assert LANES % rep == 0, rep
    base = chunk * LANES // rep
    blk = base // LANES * LANES
    if isinstance(jax.typeof(src), jax.ref.AbstractRef):
        s = src[:, blk : min(src.shape[1], blk + LANES)]
        s = _pad_lanes(s.astype(jnp.int32) if jnp.issubdtype(s.dtype, jnp.integer) else s)
    else:
        s = _pad_lanes(src)[:, blk : blk + LANES]
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return _lane_gather(s, (base - blk) + lane // rep)


def codebook_lookup(code: jax.Array, cb_row: jax.Array) -> jax.Array:
    """Decode combined codewords ``cb_flat[code]`` by lane gathers.

    code: (R, C) int32 combined codeword sel·2^B + idx per scalar;
    cb_row: (1, T) f32 flattened codebook table, T a multiple of 128
    (``flat_codebook`` pads it).  Each 128-lane chunk of ``code`` gathers
    from the table's 128-entry vreg rows (T = 128 for the paper config:
    one gather per vreg); wider tables select among their 128-entry
    pieces.  Exact: a gather moves bits."""
    r, c = code.shape
    tabs = [
        jnp.broadcast_to(cb_row[:, t0 : t0 + LANES], (r, LANES))
        for t0 in range(0, cb_row.shape[1], LANES)
    ]
    outs = []
    for c0 in range(0, c, LANES):
        w = min(LANES, c - c0)
        cc = _pad_lanes(code[:, c0 : c0 + w])
        v = _lane_gather(tabs[0], jnp.minimum(cc, LANES - 1))
        for k in range(1, len(tabs)):
            hit = cc >= k * LANES
            v = jnp.where(hit, _lane_gather(tabs[k], jnp.clip(cc - k * LANES, 0, LANES - 1)), v)
        outs.append(v[:, :w])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def flat_codebook(cb: jax.Array) -> jax.Array:
    """(N_c, 2^B) codebooks → the (1, T) f32 lookup row of
    ``codebook_lookup`` (flattened sel·2^B + idx order, lane-padded)."""
    return _pad_lanes(cb.astype(jnp.float32).reshape(1, -1))


def decode_rows(idx_b, sel_b, inv, cb_row, cfg: BCQConfig, width: int,
                start: int = 0) -> jax.Array:
    """Dequantize scalars ``start .. start+width`` of R packed LO-BCQ rows
    to f32 (R, width), 128 lanes at a time.

    idx_b (R, ≥(start+width)/2) and sel_b (R, ≥(start+width)/2L_b) are the
    packed index / selector bytes (low nibble first), inv (R, ≥(start+width)
    /L_A) the f32 per-array dequant scales — a weight row tile of the fused
    linear and a bcq4 page's (token, head) vectors alike; arrays or VMEM
    refs, read one 128-lane chunk at a time.  Each byte is spread over its
    scalars by ``expand_lanes`` and its nibble picked by lane parity (no
    stack-and-reshape), then the combined codeword is looked up."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (idx_b.shape[0], LANES), 1)
    lb = cfg.block_len
    first, off = divmod(start, LANES)
    chunks = []
    for c in range(first, -(-(start + width) // LANES)):
        ib = expand_lanes(idx_b, 2, c)
        idx = jnp.where(lane % 2 == 0, ib & 0xF, ib >> 4)
        sb = expand_lanes(sel_b, 2 * lb, c)
        sel = jnp.where((lane // lb) % 2 == 0, sb & 0xF, sb >> 4)
        vals = codebook_lookup(sel * cfg.n_entries + idx, cb_row)
        chunks.append(vals * expand_lanes(inv, cfg.array_len, c))
    out = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
    return out[:, off : off + width]


# ===================================================================== #
#  Shared page-gather attention core (paged decode + chunked prefill)   #
# ===================================================================== #

_PAGE_NK = {"bf16": 1, "int8": 2, "bcq4": 3}


def page_pool_leaves(pool: dict, kind: str) -> tuple[list, list]:
    """The (k_leaves, v_leaves) of a single-layer page pool, in the order
    the page-gather kernel consumes them."""
    if kind == "bf16":
        return [pool["k"]], [pool["v"]]
    if kind == "int8":
        return [pool["k"], pool["k_scale"]], [pool["v"], pool["v_scale"]]
    if kind == "bcq4":
        return (
            [pool["k_idx"], pool["k_sel"], pool["k_scale"]],
            [pool["v_idx"], pool["v_sel"], pool["v_scale"]],
        )
    raise ValueError(kind)


def dequant_page(kind, refs, cfg: BCQConfig, cb_ref, sx):
    """Dequantize one page's K or V to f32 head-major (Hkv, ps, D).

    bcq4 lays the page's (head, token) vectors out as rows and decodes
    them with ``decode_rows`` — exact, bit-identical to the reference
    flat-gather; ``cb_ref`` holds the (1, T) lookup row."""
    if kind == "bf16":
        return jnp.swapaxes(refs[0][0].astype(jnp.float32), 0, 1)
    if kind == "int8":
        q = refs[0][0].astype(jnp.float32)  # (ps, Hkv, D)
        s = refs[1][0]  # (ps, Hkv) f32
        return jnp.swapaxes(q * s[..., None], 0, 1)
    ps, hkv, d2 = refs[0].shape[1:]

    def rows(ref):  # (ps, Hkv, n) bytes → (Hkv·ps, n) int32, head-major
        v = jnp.swapaxes(ref[0].astype(jnp.int32), 0, 1)
        return v.reshape(hkv * ps, v.shape[-1])

    ratio = bits_to_e4m3_impl(rows(refs[2]))
    inv = jnp.where(ratio > 0, 1.0 / (ratio * sx), 0.0)
    vals = decode_rows(rows(refs[0]), rows(refs[1]), inv, cb_ref[...], cfg, 2 * d2)
    return vals.reshape(hkv, ps, 2 * d2)


def page_counts(kv_len, page_size: int, maxp: int):
    """Grid steps of each row, ``clip(ceil(kv_len/ps), 1, MAXP)``: its
    live pages, and at least one so its output block is written.  Takes
    NumPy and jax arrays alike, so the engine counts on the host the grid
    the kernel runs."""
    return ((kv_len + page_size - 1) // page_size).clip(1, maxp)


def page_schedule(kv_len: jax.Array, page_size: int, maxp: int):
    """Flat live-page schedule for the page-gather grid.

    kv_len: (B,) visible tokens per sequence.  Returns five (B·MAXP,)
    int32 arrays — for flat step t: ``sid`` the sequence it serves,
    ``pin`` the page index within that sequence, ``first``/``last``
    whether t opens/closes its sequence's online softmax, ``live``
    whether t does any work at all — and the live total, the grid's
    length.  Sequence b gets ``page_counts`` consecutive steps; steps
    beyond the live total (never run) replay the last live step's
    indices.  Built by compares: ``sid`` counts the rows that end at or
    before t and ``starts[sid]`` sums their counts, so nothing lowers to
    a loop of gathers."""
    b = kv_len.shape[0]
    counts = page_counts(kv_len.astype(jnp.int32), page_size, maxp).astype(jnp.int32)
    ends = jnp.cumsum(counts, dtype=jnp.int32)  # starts[1:]
    total = ends[b - 1]
    t = jnp.arange(b * maxp, dtype=jnp.int32)
    t_eff = jnp.minimum(t, total - 1)[:, None]
    before = ends[None, :] <= t_eff  # (B·MAXP, B): row wholly before step t
    sid = jnp.sum(before, axis=1, dtype=jnp.int32)
    pin = t_eff[:, 0] - jnp.sum(jnp.where(before, counts[None, :], 0), axis=1, dtype=jnp.int32)
    live = t < total
    first = (pin == 0) & live
    last = jnp.any(ends[None, :] == t_eff + 1, axis=1) & live
    return (sid, pin, first.astype(jnp.int32), last.astype(jnp.int32),
            live.astype(jnp.int32), total)


def _page_gather_kernel(
    bt_ref, kvl_ref, sid_ref, pin_ref, first_ref, last_ref, live_ref,
    *args, kind, cfg, ps, rep, scale, nq,
):
    nk = _PAGE_NK[kind]
    q_ref = args[0]
    k_refs = args[1 : 1 + nk]
    v_refs = args[1 + nk : 1 + 2 * nk]
    extra = args[1 + 2 * nk :]
    if kind == "bcq4":
        sx_ref, cb_ref = extra[0], extra[1]
        o_ref, m_ref, l_ref, acc_ref = extra[2:]
        k_sx, v_sx = sx_ref[0], sx_ref[1]
    else:
        cb_ref, k_sx, v_sx = None, None, None
        o_ref, m_ref, l_ref, acc_ref = extra

    t = pl.program_id(0)
    b = sid_ref[t]
    j = pin_ref[t]

    @pl.when(first_ref[t] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[t] == 1)
    def _update():
        q = q_ref[0].astype(jnp.float32)  # (Hkv, R, D), row r = rr·C + c
        kf = dequant_page(kind, k_refs, cfg, cb_ref, k_sx)  # (Hkv, ps, D)
        vf = dequant_page(kind, v_refs, cfg, cb_ref, v_sx)
        hkv, nr, _ = q.shape

        s = jnp.einsum(
            "grd,gtd->grt", q, kf, preferred_element_type=jnp.float32
        ) * scale  # (Hkv, R, ps)
        # query c sits at absolute position kv_len - C + c; page token u at
        # j·ps + u.  One mask gives decode validity (C == 1), chunk
        # causality, prefix visibility, and unwritten-tail hiding.
        tpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (hkv, nr, ps), 2)
        c = jax.lax.broadcasted_iota(jnp.int32, (hkv, nr, ps), 1) % nq
        s = jnp.where(tpos <= (kvl_ref[b] - nq) + c, s, NEG)

        m_prev = m_ref[...]  # (Hkv, R, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "grt,gtd->grd", p, vf, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(last_ref[t] == 1)
    def _done():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def page_gather_attention(
    q: jax.Array,
    pool: dict,
    block_tables: jax.Array,
    kv_len: jax.Array,
    kind: str,
    cfg: BCQConfig,
    cb: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """The shared page-gather online-softmax attention over a page pool.

    q: (B, C, H, D) queries — query c of row b sits at absolute position
    ``kv_len[b] - C + c`` and sees page token t iff ``t <= qpos`` (decode
    is C == 1 with kv_len = live tokens; chunked prefill is C = chunk with
    kv_len = n_past + C).  pool leaves: (n_pages, ps, Hkv, ...) per
    ``cache_init`` layout; block_tables (B, MAXP) int32.  Returns
    (B, C, H, D) f32.  See the module docstring for the grid schedule.

    The page BlockSpecs are double-buffered (the Pallas TPU pipeline's
    default of two buffers per input): the pipeline issues step t+1's
    K/V page DMAs — their block indices come from the scalar-prefetched
    schedule — before step t computes, and skips the copy when the index
    repeats."""
    b, nq, h, d = q.shape
    maxp = block_tables.shape[1]
    if kind == "bcq4" and d % cfg.array_len:
        # per-head-vector cache quantization shrinks L_A to d_head
        cfg = dataclasses.replace(cfg, array_len=min(cfg.array_len, d))
    k_leaves, v_leaves = page_pool_leaves(pool, kind)
    ps = k_leaves[0].shape[1]
    hkv = k_leaves[0].shape[2]
    rep = h // hkv
    assert h == hkv * rep, (h, hkv)
    # head-major rows: KV group g holds its rep query heads × C positions
    qg = q.reshape(b, nq, hkv, rep, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b, hkv, rep * nq, d)

    sid, pin, first, last, live, total = page_schedule(kv_len, ps, maxp)

    def page_spec(leaf):
        blk = (1,) + leaf.shape[1:]
        nd = leaf.ndim
        return pl.BlockSpec(
            blk,
            lambda t, bt, kvl, sid, pin, *_, _nd=nd: (bt[sid[t], pin[t]],)
            + (0,) * (_nd - 1),
        )

    def row_spec(shape):
        nd = len(shape)
        return pl.BlockSpec(
            (1,) + shape[1:],
            lambda t, bt, kvl, sid, *_, _nd=nd: (sid[t],) + (0,) * (_nd - 1),
        )

    inputs = [qg] + k_leaves + v_leaves
    in_specs = [row_spec(qg.shape)] + [page_spec(leaf) for leaf in k_leaves + v_leaves]
    if kind == "bcq4":
        sx = jnp.stack([pool["k_sx"], pool["v_sx"]]).astype(jnp.float32)
        inputs += [sx, flat_codebook(cb)]
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(inputs[-1].shape, lambda t, *_: (0, 0)),
        ]

    kernel = functools.partial(
        _page_gather_kernel,
        kind=kind, cfg=cfg, ps=ps, rep=rep, scale=d**-0.5, nq=nq,
    )
    nr = rep * nq
    scratch_shapes = [
        pltpu.VMEM((hkv, nr, 1), jnp.float32),
        pltpu.VMEM((hkv, nr, 1), jnp.float32),
        pltpu.VMEM((hkv, nr, d), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(total,),  # dynamic: only the live steps run
        in_specs=in_specs,
        out_specs=row_spec(qg.shape),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
        name="page_gather_attention",
    )(
        block_tables.astype(jnp.int32), kv_len.astype(jnp.int32),
        sid, pin, first, last, live, *inputs,
    )
    out = out.reshape(b, hkv, rep, nq, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, nq, h, d)
