"""Pallas TPU paged-attention decode kernel (vLLM-style block tables).

Decode attention over a paged KV cache, now a thin wrapper over the shared
page-gather core (``kernels.common.page_gather_attention`` — DESIGN lives
there): each sequence's pages are gathered through its block table,
prefetched as scalars so the BlockSpec index maps can DMA exactly the
referenced page per grid step, and quantized pages (int8 / packed-BCQ4)
dequantize **in-kernel** in VMEM — bcq4 via lane gathers from the
flattened codebook (``common.decode_rows``).  The grid is
**live-page-only**: sequence b contributes ``ceil(len/ps)`` steps (its
live pages), never the
``(B, MAXP)`` sweep with masked NULL-page DMAs, so per decode step the
kernel reads exactly the live packed pages of each sequence from HBM
(≈4.7 bits/scalar for BCQ4), and NULL block-table padding moves zero
bytes.

Decode is the C == 1 case of the core: a single query at absolute
position ``len - 1`` under the core's ``tpos <= qpos`` mask sees exactly
the ``len`` live tokens.

Validated in interpret mode against ``kernels.ref.paged_attention_ref``
(tests/test_paged_kernel.py); on TPU this is the drop-in decode-attention
for the paged serving engine (Runtime.paged_kernel).
"""
from __future__ import annotations

import jax

from repro.core.bcq import BCQConfig
from repro.kernels.common import NEG, dequant_page, page_gather_attention

__all__ = ["paged_attention", "NEG", "dequant_page"]


def paged_attention(
    q: jax.Array,
    pool: dict,
    block_tables: jax.Array,
    lengths: jax.Array,
    kind: str,
    cfg: BCQConfig,
    cb: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention: q (B, H, D) against a single-layer page pool.

    pool leaves: (n_pages, page_size, Hkv, ...) per ``cache_init`` layout;
    block_tables (B, MAXP) int32; lengths (B,) live tokens per sequence.
    Returns (B, H, D) f32."""
    out = page_gather_attention(
        q[:, None], pool, block_tables, lengths, kind, cfg, cb, interpret
    )
    return out[:, 0]
