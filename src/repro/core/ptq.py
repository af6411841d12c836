"""Post-training quantization of a parameter tree (W4A4 / W4A8 / W4A16).

``quantize_params`` walks a model's param pytree and fake-quantizes every
GEMM weight matrix with the frozen universal codebooks — the paper's PTQ
step (no weight updates).  Which leaves are GEMM weights is decided by the
model zoo's naming convention: 2-D+ arrays whose path ends in ``kernel``
and is not in the exclusion set (embeddings / norms / router stay bf16,
see DESIGN.md §5).

``encode_params`` produces the *packed* W4 representation used by the true
low-bit serving path (kernels/) together with per-tensor metadata.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import bcq

EXCLUDE_TOKENS = ("embed", "norm", "router", "bias", "scale", "conv", "lru_a")


def _is_gemm_weight(path: str, leaf: Any) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not path.endswith("kernel"):
        return False
    return not any(t in path for t in EXCLUDE_TOKENS)


def _walk(tree: Any, fn: Callable[[str, Any], Any], path: str = "") -> Any:
    if isinstance(tree, dict):
        return {k: _walk(v, fn, f"{path}/{k}") for k, v in tree.items()}
    return fn(path, tree)


def quantize_params(
    params: Any,
    codebooks: jax.Array,
    cfg: bcq.BCQConfig,
    predicate: Callable[[str, Any], bool] = _is_gemm_weight,
) -> Any:
    """Fake-quantize every GEMM weight in ``params`` (PTQ, no weight update).

    Weights are stored [d_in, d_out]; BCQ blocks run along the reduction
    (d_in) axis, so we quantize along axis -2 by transposing.
    """

    def fn(path, leaf):
        if not predicate(path, leaf):
            return leaf
        w = jnp.swapaxes(leaf, -1, -2)  # blocks along reduction dim
        wq = bcq.fake_quant(w, codebooks, cfg)
        return jnp.swapaxes(wq, -1, -2).astype(leaf.dtype)

    return _walk(params, fn)


def encode_params(
    params: Any,
    codebooks: jax.Array,
    cfg: bcq.BCQConfig,
    predicate: Callable[[str, Any], bool] = _is_gemm_weight,
) -> dict:
    """Packed W4 weights for the true low-bit path: path -> (Encoded, shape)."""
    out = {}

    def fn(path, leaf):
        if predicate(path, leaf):
            w = jnp.swapaxes(leaf, -1, -2)
            out[path] = (bcq.encode(w, codebooks, cfg), w.shape)
        return leaf

    _walk(params, fn)
    return out


def pack_params(
    params: Any,
    codebooks: jax.Array,
    cfg: bcq.BCQConfig,
    predicate: Callable[[str, Any], bool] = _is_gemm_weight,
) -> Any:
    """Structural conversion to the ``quant_mode='packed'`` param tree.

    Every GEMM ``kernel`` leaf (d_in, d_out) is replaced by the
    ``kernel_packed`` dict of 4-bit buffers that packed-mode models expect
    (models/layers.init_qdense layout); MoE expert stacks (E, d_in, d_out)
    pack per expert (per-expert s_X), leaves gaining a leading E axis.
    Non-GEMM leaves pass through unchanged.  Stacked leaves (layers or
    experts) pack one slice at a time (``lax.map``): the encode's
    per-codebook candidates then exist for a single (d_in, d_out) weight,
    so peak memory does not grow with the stack depth."""
    from repro.models import layers as _layers

    def pack_leaf(leaf):
        if leaf.ndim == 3:  # layer or MoE expert stack
            return jax.lax.map(lambda w: _layers.pack_weight(w, cfg, codebooks), leaf)
        return _layers.pack_weight(leaf, cfg, codebooks)

    def walk(tree, path=""):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            p = f"{path}/{k}"
            if isinstance(v, dict):
                out[k] = walk(v, p)
            elif k == "kernel" and predicate(p, v):
                out["kernel_packed"] = pack_leaf(v)
            else:
                out[k] = v
        return out

    return walk(params)


def count_quantized_bits(params: Any, cfg: bcq.BCQConfig) -> dict:
    """Storage accounting: bf16 baseline vs LO-BCQ bits (Eq. 9) per tree."""
    total, quant = 0, 0

    def fn(path, leaf):
        nonlocal total, quant
        n = int(jnp.size(leaf))
        total += n
        if _is_gemm_weight(path, leaf):
            quant += n
        return leaf

    _walk(params, fn)
    bw = cfg.bitwidth()
    return {
        "params": total,
        "gemm_params": quant,
        "bf16_bits": total * 16,
        "ptq_bits": quant * bw + (total - quant) * 16,
        "compression": (total * 16) / max(quant * bw + (total - quant) * 16, 1),
    }
