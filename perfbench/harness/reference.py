"""Plain float32 reference of the dense family.

The reference imports nothing of the program.  It reads the served
weights as the seeded 4-bit codes the harness made (``model.make_params``)
and decodes them itself with the LO-BCQ decode rule, using the benchmark's
own copy of the codebooks: ``w = C[sel][idx] / (ratio · s_x)`` with the
E4M3 ``ratio`` of each 64-wide array.  It then runs the model teacher-forced
over a prompt and the tokens the engine served for it, in float32 at the
highest matmul precision, with no activation or KV-cache quantization, no
cache and no batching: the forward pass written down once, layer by layer.

It follows the repository's dense block (pre-norm, rotary positions with
the halves rotated, GQA, the tanh-approximated GELU or SwiGLU MLP, biases
on q/k/v only where the configuration says so, a tied or untied LM head
over the padded vocabulary), which departs from the published GPT-3 and
StarCoder2 blocks as ``PERF.md`` lists.

``quant=True`` is the control: the same forward with every linear's
input and every key and value vector fake-quantized to LO-BCQ with 3-bit
indices (each codebook thinned to 8 of its 16 levels), one bit below the
4-bit format the configuration states.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

# the 8 of 16 sorted levels the 3-bit control keeps: both extremes, evenly
THIN_3BIT = (0, 2, 4, 6, 9, 11, 13, 15)
BUCKET = 256  # sequences pad to a multiple of this (one compile per bucket)


def e4m3_value(code):
    """uint8 E4M3 bit pattern -> float32 (non-negative scales only)."""
    import jax.numpy as jnp

    c = code.astype(jnp.int32)
    e, m = c >> 3, (c & 7).astype(jnp.float32)
    sub = m / 8.0 * 2.0**-6
    nrm = (1.0 + m / 8.0) * jnp.exp2((e - 7).astype(jnp.float32))
    return jnp.where(e == 0, sub, nrm)


def e4m3_round(v):
    """Round positive values to the E4M3 grid (OCP: max 448, subnormals)."""
    import jax.numpy as jnp

    _, e = jnp.frexp(v)
    e = jnp.clip(e - 1, -6, 8).astype(jnp.float32)
    ulp = jnp.exp2(e - 3.0)
    return jnp.minimum(jnp.round(v / ulp) * ulp, 448.0)


def nibbles(b):
    import jax.numpy as jnp

    b = b.astype(jnp.int32)
    return jnp.stack([b & 15, b >> 4], axis=-1).reshape(b.shape[:-1] + (2 * b.shape[-1],))


def decode_weight(pk: dict, cb, fmt: dict):
    """A packed (N, K) weight -> float32 (K, N)."""
    import jax.numpy as jnp

    lb, la, e = fmt["block_len"], fmt["array_len"], 2 ** fmt["index_bits"]
    idx = nibbles(pk["idx"])
    k = idx.shape[-1]
    sel = nibbles(pk["sel"])[..., : k // lb]
    code = jnp.repeat(sel, lb, axis=-1) * e + idx
    ratio = jnp.repeat(e4m3_value(pk["scale"]), la, axis=-1)
    return (cb.reshape(-1)[code] / (ratio * pk["s_x"])).T


def bcq_fake(x, levels, fmt: dict, s_x=None, valid=None):
    """LO-BCQ quantize-dequantize of ``x`` along its last axis with the
    given (sorted) codebook levels: per-tensor scale (over the ``valid``
    rows), E4M3 per-array scales, per-block codebook choice (the first
    wins ties), nearest level per scalar."""
    import jax.numpy as jnp

    la, lb = min(fmt["array_len"], x.shape[-1]), fmt["block_len"]
    cmax = float(2 ** (fmt["codeword_bits"] - 1) - 1)
    if s_x is None:
        a = jnp.abs(x) if valid is None else jnp.where(valid[:, None], jnp.abs(x), 0.0)
        amax = jnp.max(a)
        s_x = jnp.where(amax > 0, cmax / amax, 1.0)
    arr = x.reshape(x.shape[:-1] + (x.shape[-1] // la, la))
    amax_a = jnp.max(jnp.abs(arr), axis=-1)
    s_a = jnp.where(amax_a > 0, cmax / amax_a, s_x)
    ratio = jnp.maximum(e4m3_round(s_a / s_x), 2.0**-9)
    scale = (ratio * s_x)[..., None]
    y = (arr * scale).reshape(arr.shape[:-1] + (la // lb, lb))
    thr = 0.5 * (levels[:, 1:] + levels[:, :-1])  # (Nc, E-1)
    best_q, best_err = None, None
    for c in range(levels.shape[0]):
        idx = jnp.zeros(y.shape, jnp.int32)
        for t in range(thr.shape[1]):
            idx = idx + (y >= thr[c, t]).astype(jnp.int32)
        q = levels[c][idx]
        err = jnp.sum((y - q) ** 2, axis=-1, keepdims=True)
        if best_q is None:
            best_q, best_err = q, err
        else:
            better = err < best_err
            best_q = jnp.where(better, q, best_q)
            best_err = jnp.where(better, err, best_err)
    return (best_q.reshape(arr.shape) / scale).reshape(x.shape)


def _norm(x, p, kind: str):
    import jax.numpy as jnp

    if kind == "rmsnorm":
        y = x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6))
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        y = (x - mu) / jnp.sqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True) + 1e-6)
    y = y * p["scale"].astype(jnp.float32)
    if "nbias" in p:
        y = y + p["nbias"].astype(jnp.float32)
    return y


def _rope(x, theta: float):
    """x (S, H, D); position s rotates pair (d, d + D/2) by s·theta^(-2d/D)."""
    import jax.numpy as jnp

    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(x, p, cb, n_valid, cfg_items, quant):
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    fmt = dict(cfg["fmt"])
    s, d = x.shape
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    valid = jnp.arange(s) < n_valid
    if quant:
        levels = cb[:, jnp.asarray(THIN_3BIT)]
        fq = lambda v: bcq_fake(v, levels, fmt, valid=valid)  # noqa: E731
        kvq = lambda v: bcq_fake(v, levels, fmt, s_x=jnp.float32(1.0))  # noqa: E731
    else:
        fq = kvq = lambda v: v  # noqa: E731

    def lin(v, q):
        y = fq(v) @ decode_weight(q["kernel_packed"], cb, fmt)
        return y + q["bias"].astype(jnp.float32) if "bias" in q else y

    a = p["attn"]
    hn = _norm(x, p["ln1"], cfg["norm"])
    q = _rope(lin(hn, a["wq"]).reshape(s, h, hd), cfg["rope_theta"])
    k = kvq(_rope(lin(hn, a["wk"]).reshape(s, hkv, hd), cfg["rope_theta"]))
    v = kvq(lin(hn, a["wv"]).reshape(s, hkv, hd))
    rep = h // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -1e30)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v).reshape(s, h * hd)
    x = x + lin(o, a["wo"])
    hn = _norm(x, p["ln2"], cfg["norm"])
    m = p["mlp"]
    if cfg["act"] == "swiglu":
        f = jax.nn.silu(lin(hn, m["wg"])) * lin(hn, m["wi"])
    else:
        f = jax.nn.gelu(lin(hn, m["wi"]), approximate=True)
    return x + lin(f, m["wo"])


@functools.partial(jax.jit, static_argnames=("norm",))
def _head(x_rows, ln_f, w_head, norm):
    return _norm(x_rows, ln_f, norm) @ w_head.astype("float32")


def _cfg_items(cfg: dict, fmt: dict):
    keys = ("n_heads", "n_kv_heads", "rope_theta", "norm", "act")
    items = {k: cfg[k] for k in keys}
    items["head_dim"] = cfg.get("d_head") or cfg["d_model"] // cfg["n_heads"]
    items["fmt"] = tuple(sorted(fmt.items()))
    return tuple(sorted(items.items()))


def logits_at(params, cfg: dict, fmt: dict, tokens: np.ndarray, rows: np.ndarray,
              quant: bool = False):
    """Reference logits (len(rows), V_padded) at positions ``rows`` of the
    teacher-forced sequence ``tokens``."""
    import jax.numpy as jnp

    n = len(tokens)
    padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    padded[:n] = tokens
    items = _cfg_items(cfg, fmt)
    cb = params["codebooks"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["kernel"].astype(jnp.float32)[jnp.asarray(padded)]
        for layer in range(cfg["n_layers"]):
            p = jax.tree.map(lambda a, _l=layer: a[_l], params["layers"])
            x = _layer(x, p, cb, n, items, quant)
        w = params["lm_head"]["kernel"] if "lm_head" in params else params["embed"]["kernel"].T
        out = _head(x[jnp.asarray(rows)], params["ln_f"], w, cfg["norm"])
    return np.asarray(out, np.float32)


def token_readings(params, cfg: dict, fmt: dict, prompt, served, control: bool = False) -> dict:
    """Per served token, read off the reference at its position: ``gap``,
    how far the served token's logit lies below the reference's best;
    ``margin``, the best's lead over the runner-up; ``spread``, the
    standard deviation of the reference's logits there.  With ``control``,
    also ``control_gap``: the gap of the token that the 3-bit control
    forward puts first at the same position."""
    served = np.asarray(served, np.int64)
    tokens = np.concatenate([np.asarray(prompt, np.int64), served[:-1]])
    rows = len(prompt) - 1 + np.arange(len(served))
    ref = logits_at(params, cfg, fmt, tokens, rows)
    best = ref.max(axis=-1)
    at = np.arange(len(rows))
    top2 = np.partition(ref, -2, axis=-1)[:, -2:]
    out = {
        "gap": best - ref[at, served],
        "margin": top2[:, 1] - top2[:, 0],
        "spread": ref.std(axis=-1),
    }
    if control:
        pick = np.argmax(logits_at(params, cfg, fmt, tokens, rows, quant=True), axis=-1)
        out["control_gap"] = best - ref[at, pick]
    return out
