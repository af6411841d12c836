"""Pallas TPU chunked-prefill attention kernel (paged prefix, causal chunk).

Prefill attention for ONE query chunk of a prompt whose earlier tokens
already live in KV pages — now a thin wrapper over the shared page-gather
core (``kernels.common.page_gather_attention`` — DESIGN lives there).  The
chunk's queries attend to every page the sequence references through its
scalar-prefetched block table — prefix-hit pages written by *other*
requests included — with quantized pages dequantized **in-kernel** (bcq4
via lane gathers from the flattened codebook) and a **live-page-only grid**:
sequence b contributes ``ceil((n_past+C)/ps)`` steps, so NULL table
padding and absent sequences move zero HBM bytes.

The causal structure falls out of absolute positions: query c of the
chunk sits at position ``n_past + c`` and may see page token t iff
``t <= n_past + c`` — prefix tokens are visible to the whole chunk, chunk
tokens mask causally, and garbage past the written tail is invisible.
This is the compute half of prefix caching: the engine never re-runs the
transformer over prefix-hit tokens, and this kernel lets the uncached
suffix attend to the shared pages without dequantizing them to HBM first.

Validated in interpret mode against ``kernels.ref.chunked_prefill_ref``
(tests/test_chunked_prefill.py); on TPU this is the drop-in chunk
attention for PagedEngine(chunked_prefill=True) with Runtime.paged_kernel.
"""
from __future__ import annotations

import jax

from repro.core.bcq import BCQConfig
from repro.kernels.common import page_gather_attention

__all__ = ["chunked_prefill"]


def chunked_prefill(
    q: jax.Array,
    pool: dict,
    block_tables: jax.Array,
    n_past: jax.Array,
    kind: str,
    cfg: BCQConfig,
    cb: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked prefill attention: q (B, C, H, D) against a single-layer pool.

    pool leaves: (n_pages, page_size, Hkv, ...) per ``cache_init`` layout,
    with the chunk's own K/V already written into its pages;
    block_tables (B, MAXP) int32; n_past (B,) tokens in pages BEFORE this
    chunk (query c is at absolute position n_past[b] + c; the sequence
    must reference ≥ n_past + C written tokens through its table).
    Returns (B, C, H, D) f32."""
    kv_len = n_past.astype("int32") + q.shape[1]
    return page_gather_attention(
        q, pool, block_tables, kv_len, kind, cfg, cb, interpret
    )
