"""The work a step must do, from the model's shapes alone: operations and
bytes of each fused W4A4 linear and of page-gather attention, and the
model FLOPs of a token.  Nothing here depends on how a kernel tiles or
pads: padding rows, tile re-fetches and dead grid steps do not count.

Conventions:
* a linear of K inputs and N outputs over M live rows does 2·M·K·N
  operations and must read its weights once at their packed size (4-bit
  index, 3-bit selector per 8-wide block stored two per byte, one E4M3
  scale byte per 64-wide array) plus the activations in and out at the
  compute dtype (2 bytes);
* attention over a context of T tokens does 4·H·D·T operations per query
  (scores and the weighted sum) and must read the live context's bcq4
  pages at their logical size: whole pages of ``page_size`` tokens, each
  token holding 2·Hkv vectors of D/2 + D/16 + D/64 bytes, plus the query
  and the float32 output.
"""
from __future__ import annotations

ACT_BYTES = 2  # bf16 activations in and out of a linear


def head_dim(cfg: dict) -> int:
    return cfg.get("d_head") or cfg["d_model"] // cfg["n_heads"]


def vocab_padded(cfg: dict) -> int:
    """The vocabulary rows the model holds (padded to a multiple of 256)."""
    return -(-cfg["vocab"] // 256) * 256


def linears(cfg: dict) -> list[tuple[int, int]]:
    """(K, N) of every fused linear of one layer, in program order."""
    d, f, hd = cfg["d_model"], cfg["d_ff"], head_dim(cfg)
    h, hkv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    mlp = [(d, f), (d, f), (f, d)] if cfg["act"] == "swiglu" else [(d, f), (f, d)]
    return [(d, h), (d, hkv), (d, hkv), (h, d)] + mlp


def packed_weight_bytes(k: int, n: int) -> float:
    return n * (k / 2 + k / 16 + k / 64)


def linear_least_s(cfg: dict, rows: int, peak_ops: float, bw: float) -> float:
    """Least time of one launch's linears: every layer's, at ``rows``
    live rows, each bounded by the larger of compute and memory."""
    t = 0.0
    for k, n in linears(cfg):
        ops = 2.0 * rows * k * n
        byt = packed_weight_bytes(k, n) + ACT_BYTES * rows * (k + n)
        t += max(ops / peak_ops, byt / bw)
    return cfg["n_layers"] * t


def kv_token_bytes(cfg: dict) -> float:
    """Logical bcq4 bytes of one token's K and V in one layer."""
    hd = head_dim(cfg)
    return 2 * cfg["n_kv_heads"] * (hd / 2 + hd / 16 + hd / 64)


def attention_work(cfg: dict, page_size: int, n_past: int, n_q: int) -> tuple[float, float]:
    """(operations, bytes) of one row's attention in one layer: ``n_q``
    causal queries at positions n_past .. n_past+n_q-1 over the pages
    holding n_past + n_q tokens."""
    hd, h = head_dim(cfg), cfg["n_heads"]
    seen = n_q * n_past + n_q * (n_q + 1) / 2  # Σ visible tokens over queries
    ops = 4.0 * h * hd * seen
    pages = -(-(n_past + n_q) // page_size)
    byt = pages * page_size * kv_token_bytes(cfg) + n_q * h * hd * (2 + 4)
    return ops, byt


def attention_least_s(cfg: dict, page_size: int, launch_rows, peak_ops: float, bw: float) -> float:
    """Least time of one launch's attention over every layer:
    ``launch_rows`` lists (n_past, n_q) per live row."""
    ops = byt = 0.0
    for n_past, n_q in launch_rows:
        o, b = attention_work(cfg, page_size, n_past, n_q)
        ops += o
        byt += b
    return cfg["n_layers"] * max(ops / peak_ops, byt / bw)


def token_flops(cfg: dict, context: int, with_head: bool) -> float:
    """Model FLOPs of one token at position ``context`` (tokens before it):
    2 per weight of the linears (and the LM head where the token yields
    logits) plus attention over its context and itself."""
    per_layer = sum(k * n for k, n in linears(cfg))
    head = cfg["d_model"] * vocab_padded(cfg) if with_head else 0
    attn = 4.0 * cfg["n_heads"] * head_dim(cfg) * (context + 1)
    return 2.0 * (cfg["n_layers"] * per_layer + head) + cfg["n_layers"] * attn
