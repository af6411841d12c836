#!/usr/bin/env python3
"""Chip smoke test: the W4A4 paged serving path on one TPU chip.

Serves full-width gpt3_126m (12 layers, d=768, d_ff=3072, vocab 50304,
seeded random weights packed to 4 bits with the committed universal
codebooks) through the main path — ``Runtime(quant_mode="packed",
fused_linear=True, paged_kernel=True, cache_kind="bcq4")`` behind
``PagedEngine(chunked_prefill=True, pipeline_depth=2, strict=True)`` — and
checks what comes out:

1. the backend switches resolve to native Pallas (no interpret mode);
2. the fused W4A4 linear (K=768 and K=3072; and StarCoder2-3B's
   K=12288 → 3072 at M = 128, 256 and 512, where K is split into blocks)
   and the page-gather attention (decode and chunked prefill over bcq4
   pages, gpt3's 12 heads and StarCoder2's 24 query heads over 2 KV heads
   of 128 at 1 and 8 rows) agree with their ``ref.py`` oracles within the
   stated bound, and a permuted codebook fails it;
3. 8 requests (prompts of 64–384 tokens, 32 new tokens each, two sharing a
   128-token prefix) finish without error, the page audit is clean, and a
   second identical run retraces nothing;
4. the compiled decode and chunk-prefill programs
   (``PagedEngine.lower_steps``) contain the fused-linear and page-gather
   Pallas kernels (``tpu_custom_call``);
5. the engine's first-token logits (``Request.keep_prompt_logits``) agree
   with the same requests served through the jnp route
   (``paged_kernel=False, fused_linear=False``), and the kernel route fed
   a permuted codebook does not.

Run from the repo root of a machine with a TPU:  ``python3 chip_smoke.py``.
It refuses to run where JAX finds no TPU.  The last stdout line is one JSON
object ``{"ok": true, "device": {...}}``; any failure exits non-zero.

Tolerances (why each is what it is):

* Kernel vs oracle.  Both sides decode identical 4-bit codes to identical
  f32 operands (lookups and E4M3 scales are exact); what may differ is the
  MXU pass.  At default precision each f32 operand may be rounded to bf16
  (relative error ≤ 2^-9 each, ≤ 2^-8 per product), so every output
  element must satisfy ``|out − ref| ≤ 2^-7 · Σ_k |â_k|·|ŵ_k|`` (2× that
  rounding bound; the oracle runs at HIGHEST precision).  Attention: a
  score error δ moves the softmax output by at most 2δ·max|v|, so each
  output row must satisfy ``|out − ref| ≤ (2·δ + 2^-7)·max|v|`` with
  ``δ = 2^-7 · scale · max_t Σ_d |q_d|·|k_td|``.  A permuted codebook
  moves outputs by O(Σ|â||ŵ|) and O(max|v|) and fails both bounds — the
  script checks that too.
* Engine logits.  The jnp route rounds both dequantized operands to bf16
  before its einsum and re-quantizes every activation to 4 bits, so a
  one-ulp difference can flip a 4-bit code and grow through 12 layers of a
  random-weight model whose logits are noise-sized.  With f32 compute the
  two routes agree to ~4e-5; with the served bf16 compute they differ by a
  relative L2 of 0.13–0.21 (smoke-size CPU run) and 0.143–0.160 (this
  script on a v5e), while the kernel route fed a permuted codebook gives
  1.26–1.51 (smoke-size CPU run) and 1.375–1.441 (this script on a v5e).
  The first-token logits must agree to ``‖Δ‖/‖ref‖ ≤ LOGIT_RTOL = 0.5``
  per request, and the permuted codebook must exceed it on every request:
  3× the largest sound reading, under half the smallest wrong one.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "gpt3_126m"
WIDE_ARCH = "starcoder2_3b"  # K = 12288 linears, 12 query heads a KV head
SEED = 0
N_REQ = 8
NEW_TOKENS = 32
PROMPT_RANGE = (64, 384)
SHARED_PREFIX = 128
PAGE = 16
CHUNK = 64
MAX_LEN = 448
LOGIT_RTOL = 0.5


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _highest():
    import jax

    return jax.default_matmul_precision("highest")


# ------------------------------------------------------------ kernels
def check_linear(cb, cfg, k: int, n: int, m: int = 128) -> dict:
    """Fused W4A4 linear on the chip vs ``ref.fused_linear_ref``."""
    import jax
    import jax.numpy as jnp

    from repro.core import bcq
    from repro.kernels import ops, ref
    from repro.models.layers import pack_weight

    kx, kw = jax.random.split(jax.random.PRNGKey(SEED + k))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.02
    pw = ops.packed_operand(pack_weight(w, cfg, cb))
    s_x = bcq.tensor_scale(x, cfg)
    with _highest():
        want = ref.fused_linear_ref(
            x, pw.idx_packed, pw.sel_packed, pw.inv_scale, cb, cfg, s_x
        )
        idx_p, sel_p, ratio = ref.quantize_ref(x, cb, cfg, s_x)
        a_hat = ref.decode_ref(idx_p, sel_p, ref.inv_scale(ratio, s_x), cb, cfg)
        w_hat = ref.decode_ref(pw.idx_packed, pw.sel_packed, pw.inv_scale, cb, cfg)
        bound = 2.0**-7 * (jnp.abs(a_hat) @ jnp.abs(w_hat).T)
    got = ops.w4a4_linear_fused(x, pw, cb, cfg, s_x=s_x)
    perm = jax.random.permutation(jax.random.PRNGKey(SEED + 1), cb.size)
    bad = ops.w4a4_linear_fused(x, pw, cb.reshape(-1)[perm].reshape(cb.shape), cfg, s_x=s_x)
    return _verdict(f"linear M={m} K={k} N={n}", got, want, bound, bad)


def _verdict(name, got, want, bound, permuted) -> dict:
    """Compare ``got`` with the oracle ``want`` under the elementwise
    ``bound``; ``permuted`` (the kernel fed a permuted codebook) must
    break the bound somewhere."""
    got, want, bound, permuted = (
        np.asarray(v, np.float32) for v in (got, want, bound, permuted)
    )
    err = np.abs(got - want)
    return {
        "name": name,
        "shape": list(got.shape),
        "finite": bool(np.isfinite(got).all()),
        "bitwise": bool(np.array_equal(got, want)),
        "max_err_over_bound": float(np.max(err / np.maximum(bound, 1e-30))),
        "frac_over_bound": float(np.mean(err > bound)),
        "max_abs_err": float(err.max()),
        "ok": bool(np.isfinite(got).all() and np.all(err <= bound)),
        "permuted_fails": bool(np.any(np.abs(permuted - want) > bound)),
    }


def _bcq4_pool(cb, cfg, n_pages, hkv, d):
    import jax
    import jax.numpy as jnp

    from repro.models import layers

    kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 7))
    k = jax.random.normal(kk, (n_pages, PAGE, hkv, d))
    v = jax.random.normal(kv, (n_pages, PAGE, hkv, d))
    pool = layers.cache_init(n_pages, PAGE, hkv, d, "bcq4", cfg)
    pool = layers.cache_sx_calibrate(pool, k, v, "bcq4", cfg)
    return layers.cache_write(pool, k, v, 0, "bcq4", cfg, cb)


def _attn_bound(q, pool, bt, cfg, cb):
    """Per (row, head) bound (2·δ + 2^-7)·max|v| — see the module doc."""
    import jax.numpy as jnp

    from repro.kernels import ref

    kf = ref._dequant_pool_ref(dict(pool, _cb=cb), "k", "bcq4", cfg)[bt]
    vf = ref._dequant_pool_ref(dict(pool, _cb=cb), "v", "bcq4", cfg)[bt]
    b, maxp, ps, hkv, d = kf.shape
    rep = q.shape[2] // hkv  # q (B, C, H, D): each KV head serves rep query heads
    kf = jnp.repeat(kf.reshape(b, maxp * ps, hkv, d), rep, axis=2)
    vmax = jnp.repeat(jnp.max(jnp.abs(vf.reshape(b, -1, hkv, d)), axis=(1, 3)), rep, axis=1)  # (B, H)
    with _highest():
        dot = jnp.einsum("bchd,bthd->bcht", jnp.abs(q), jnp.abs(kf))
    delta = 2.0**-7 * d**-0.5 * jnp.max(dot, axis=-1)  # (B, C, H)
    return (2 * delta + 2.0**-7)[..., None] * vmax[:, None, :, None]


def check_attention(cb, cfg, h: int, hkv: int, d: int, b: int = 8) -> list[dict]:
    """Paged decode + chunked prefill over bcq4 pages vs ``ref.py``: ``b``
    rows of ``h`` query heads over ``hkv`` KV heads of ``d``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.chunked_prefill import chunked_prefill
    from repro.kernels.paged_attention import paged_attention

    n_pages, maxp = 64, 8
    tag = f"H={h} Hkv={hkv} D={d} rows={b}"
    pool = _bcq4_pool(cb, cfg, n_pages, hkv, d)
    rng = np.random.default_rng(SEED)
    bt = jnp.asarray(rng.integers(1, n_pages, (b, maxp)), jnp.int32)
    perm = jax.random.permutation(jax.random.PRNGKey(SEED + 2), cb.size)
    cb_bad = cb.reshape(-1)[perm].reshape(cb.shape)
    out = []

    lengths = jnp.asarray(rng.integers(1, maxp * PAGE + 1, b), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(SEED + 3), (b, h, d))
    with _highest():
        want = ref.paged_attention_ref(q, pool, bt, lengths, "bcq4", cfg, cb)
    bound = _attn_bound(q[:, None], pool, bt, cfg, cb)[:, 0]
    got = paged_attention(q, pool, bt, lengths, "bcq4", cfg, cb)
    bad = paged_attention(q, pool, bt, lengths, "bcq4", cfg, cb_bad)
    out.append(_verdict(f"paged_attention bcq4 {tag}", got, want, bound, bad))

    c = CHUNK
    n_past = jnp.asarray(rng.integers(0, maxp * PAGE // PAGE - c // PAGE + 1, b) * PAGE, jnp.int32)
    qc = jax.random.normal(jax.random.PRNGKey(SEED + 4), (b, c, h, d))
    with _highest():
        want = ref.chunked_prefill_ref(qc, pool, bt, n_past, "bcq4", cfg, cb)
    bound = _attn_bound(qc, pool, bt, cfg, cb)
    got = chunked_prefill(qc, pool, bt, n_past, "bcq4", cfg, cb)
    bad = chunked_prefill(qc, pool, bt, n_past, "bcq4", cfg, cb_bad)
    out.append(_verdict(f"chunked_prefill bcq4 {tag}", got, want, bound, bad))
    return out


def kernel_checks(cb, cfg, arch, wide) -> list[dict]:
    """Every kernel check: the served arch's linears and attention, and the
    wide arch's K = d_ff linear at one to four M tiles and its GQA
    attention (rep = H/Hkv query heads a KV head) at 1 and 8 rows."""
    checks = [
        check_linear(cb, cfg, arch.d_model, 3 * arch.d_model),
        check_linear(cb, cfg, arch.d_ff, arch.d_model),
    ] + check_attention(cb, cfg, arch.n_heads, arch.n_kv_heads, arch.head_dim)
    checks += [check_linear(cb, cfg, wide.d_ff, wide.d_model, m) for m in (128, 256, 512)]
    for b in (1, 8):
        checks += check_attention(cb, cfg, wide.n_heads, wide.n_kv_heads, wide.head_dim, b)
    return checks


# ------------------------------------------------------------ serving
def make_requests(vocab: int, prompt_range=PROMPT_RANGE, shared=SHARED_PREFIX):
    """N_REQ seeded prompts; the last one shares the first one's prefix.
    Each request keeps its prompt's last-position logits."""
    from repro.serving.generate import Request

    rng = np.random.default_rng(SEED)
    lens = rng.integers(prompt_range[0], prompt_range[1] + 1, N_REQ)
    lens[[0, -1]] = np.maximum(lens[[0, -1]], shared + PAGE)  # prefix + own tail
    prompts = [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]
    prompts[-1][:shared] = prompts[0][:shared]
    return lambda max_new: [
        Request(rid=i, prompt=p.copy(), max_new=max_new, keep_prompt_logits=True)
        for i, p in enumerate(prompts)
    ]


def serve(api, params, reqs, max_len=MAX_LEN, chunk=CHUNK):
    """Serve ``reqs`` through the main-path engine.  The last request
    (which shares the first one's prefix) is submitted once the first has
    finished its prefill, so its admission claims the shared pages.
    Returns (engine, first-token logits by rid)."""
    from repro.serving.engine import PagedEngine

    engine = PagedEngine(
        api, params, n_slots=N_REQ, max_len=max_len, page_size=PAGE,
        chunked_prefill=True, prefill_chunk=chunk, pipeline_depth=2,
        strict=True,
    )
    for r in reqs[:-1]:
        engine.submit(r)
    while not reqs[0].out:
        engine.step()
    engine.submit(reqs[-1])
    engine.run_to_completion()
    return engine, {r.rid: r.prompt_logits for r in reqs}


def check_served(engine, reqs, max_new) -> list[str]:
    """What is wrong with a finished serving run (empty when nothing)."""
    from repro.serving.audit import audit_engine

    bad = []
    done = sorted(r.rid for r in engine.finished)
    if done != list(range(N_REQ)):
        bad.append(f"finished rids {done}")
    for r in reqs:
        if r.error is not None or len(r.out) != max_new + 1:
            bad.append(f"rid {r.rid}: error {r.error!r}, {len(r.out)} tokens")
    report = audit_engine(engine)
    if not report.ok:
        bad.append(f"page audit: {report.to_dict()}")
    hits = engine.snapshot()["counters"].get("prefix_hits", 0)
    if hits < SHARED_PREFIX // PAGE:
        bad.append(f"prefix claim path not exercised ({hits} page hits)")
    return bad


def kernels_in(compiled_text: str) -> dict:
    return {
        "tpu_custom_call": compiled_text.count("tpu_custom_call"),
        "bcq_linear": "bcq_linear" in compiled_text,
        "page_gather_attention": "page_gather_attention" in compiled_text,
    }


def compiled_programs(engine) -> dict:
    """Which kernels the engine's compiled decode and chunk-prefill steps hold."""
    return {
        name: kernels_in(lowered.compile().as_text())
        for name, lowered in engine.lower_steps().items()
    }


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r} — refusing to run",
            file=sys.stderr,
        )
        return 2
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax.numpy as jnp

    from repro.configs.base import get_arch
    from repro.core import ptq
    from repro.core.bcq import BCQConfig
    from repro.core.calibrate import default_universal_codebooks
    from repro.kernels import common, ops
    from repro.models import zoo
    from repro.models.layers import Runtime

    failures = []
    log(f"device {dev.device_kind} ({dev.platform}), {len(jax.devices())} device(s)")
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache {cache_dir}: {n_cached} entries at start")

    # 1. the backend switches resolve to native Pallas on the chip
    native = {"impl": ops._default_impl(), "interpret": common.resolve_interpret(None)}
    log(f"backend switches: {native}")
    if native != {"impl": "pallas", "interpret": False}:
        failures.append(f"backend switches not native: {native}")

    cfg = BCQConfig()
    cb = default_universal_codebooks(cfg).as_jnp()
    arch = get_arch(ARCH)

    # 2. kernels vs oracles
    t0 = time.perf_counter()
    checks = kernel_checks(cb, cfg, arch, get_arch(WIDE_ARCH))
    for c in checks:
        log(f"kernel check {json.dumps(c)}")
        if not c["ok"]:
            failures.append(f"{c['name']} outside its bound")
        if not c["permuted_fails"]:
            failures.append(f"{c['name']}: a permuted codebook passes the bound")
    log(f"kernel checks took {time.perf_counter() - t0:.3f} s (compile included)")

    # 3. serve full-width gpt3_126m through the main path
    rt = Runtime(
        quant_mode="packed", bcq_cfg=cfg, fused_linear=True, paged_kernel=True,
        cache_kind="bcq4",
    )
    api = zoo.build(arch, rt)
    base = zoo.build(arch, Runtime(quant_mode="none", param_dtype=jnp.float32))
    t0 = time.perf_counter()
    params = ptq.pack_params(base.init(jax.random.PRNGKey(SEED)), cb, cfg)
    params["codebooks"] = cb
    jax.block_until_ready(params)
    log(f"PTQ of {ARCH} took {time.perf_counter() - t0:.3f} s (compile included)")
    requests = make_requests(arch.vocab)
    max_new = NEW_TOKENS - 1  # the prefill emits the first of the 32

    t0 = time.perf_counter()
    engine, _ = serve(api, params, requests(max_new))
    t_warm = time.perf_counter() - t0
    reqs = requests(max_new)
    t0 = time.perf_counter()
    engine, first = serve(api, params, reqs)
    t_run = time.perf_counter() - t0
    failures += check_served(engine, reqs, max_new)
    traces = engine.trace_counts()
    hits = engine.snapshot()["counters"].get("prefix_hits", 0)
    log(
        f"served {N_REQ} requests ({[len(r.prompt) for r in reqs]} prompt tokens, "
        f"{NEW_TOKENS} new each): warm-up run {t_warm:.3f} s (compile included), "
        f"measured run {t_run:.3f} s, retraces after warm-up {traces}, "
        f"prefix page hits {hits}"
    )
    if any(traces.values()):
        failures.append(f"retraced after warm-up: {traces}")

    # 4. the kernels are on the compiled path
    t0 = time.perf_counter()
    progs = compiled_programs(engine)
    log(f"compiled programs ({time.perf_counter() - t0:.3f} s): {progs}")
    for name, k in progs.items():
        if not (k["bcq_linear"] and k["page_gather_attention"]):
            failures.append(f"{name} program lacks the Pallas kernels: {k}")

    # 5. first-token logits vs the jnp route; the kernel route fed a
    # permuted codebook must fail the same bound on every request
    api_jnp = zoo.build(arch, dataclasses.replace(rt, paged_kernel=False, fused_linear=False))
    t0 = time.perf_counter()
    _, first_jnp = serve(api_jnp, params, requests(0))
    log(f"jnp-route prefill took {time.perf_counter() - t0:.3f} s (compile included)")
    perm = jax.random.permutation(jax.random.PRNGKey(SEED + 5), cb.size)
    _, first_bad = serve(api, dict(params, codebooks=cb.reshape(-1)[perm].reshape(cb.shape)), requests(0))
    for rid in range(N_REQ):
        a, b, bad = first[rid], first_jnp[rid], first_bad[rid]
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        rel_bad = float(np.linalg.norm(bad - b) / np.linalg.norm(b))
        log(
            f"first-token logits rid={rid}: rel_l2={rel:.6f} bitwise={bool(np.array_equal(a, b))} "
            f"argmax {int(a.argmax())} vs {int(b.argmax())}; permuted codebook rel_l2={rel_bad:.6f}"
        )
        if not (np.isfinite(a).all() and rel <= LOGIT_RTOL):
            failures.append(f"rid {rid} first-token logits rel_l2 {rel} > {LOGIT_RTOL}")
        if not rel_bad > LOGIT_RTOL:
            failures.append(f"rid {rid}: a permuted codebook passes the logit bound ({rel_bad})")

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    if failures:
        for f in failures:
            log(f"FAIL {f}")
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
