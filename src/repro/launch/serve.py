"""Batched W4A4 serving driver (the paper-kind end-to-end example).

Loads (or trains a few steps of) a model, PTQs weights with the frozen
universal codebooks, then serves batched requests: prefill the prompt
batch, greedy-decode N tokens with on-the-fly LO-BCQ activation
quantization at every GEMM.  Reports tokens/s and compares W4A4 outputs to
the bf16 baseline.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt3_126m --smoke \
      --batch 4 --prompt-len 32 --gen 16

``--paged`` routes the W4A4 pass through the paged serving engine
(serving/engine.py): page-pool KV cache, prefix caching, admission
control — and verifies its greedy outputs equal the contiguous path.
State-checkpoint families (ssm / hybrid / enc-dec, e.g. --arch
mamba2_130m, recurrentgemma_9b, whisper_base) serve through
serving/state_engine.py instead: typed ``state`` pages checkpoint the
O(1) recurrent state at page boundaries (preemption replays ≤ page_size
tokens), and enc-dec publishes its encoder output once per distinct
audio into a read-only ``shared_ro`` page (docs/SERVING.md).  A family
with no paged path (e.g. pixtral_12b) raises a typed
``UnsupportedModelError`` naming the family and the supported list.
``--chunked-prefill`` additionally serves through chunk-at-a-time
admission (prefill spread across ticks, prefix-hit pages never
recomputed, prompt length no longer capped by the prefill slab);
``--prefill-chunk N`` sets the chunk size (a page multiple).
``--kv-bucket N`` bounds each contiguous decode step's cache read to the
written prefix rounded up to N (bucketed dequantization).
``--pipeline-depth D`` sets the paged tick loop's dispatch queue depth
(default 2: tick t+1's decode launch is enqueued before syncing tick t,
so host scheduling overlaps device compute; 1 restores the synchronous
loop — tokens are bit-identical at any depth).
``--packed`` also serves through the true-storage path: weights held as
packed 4-bit buffers and every linear dispatched to the fused
quantize→decode→GEMM kernel (kernels/bcq_linear.py; ``--unfused`` falls
back to in-graph decode_packed_weight + einsum for comparison).
``--best-of N`` serves every prompt as an N-way SEQUENCE FORK through the
paged engine: one prefill, then N sibling decode branches that share all
prompt pages by refcount (zero copies, zero recompute) and copy-on-write
only their divergent tail page.  ``--temperature T`` (with ``--top-k`` /
``--seed``) turns on seeded temperature sampling — deterministic per
(seed, sample index, position), so runs reproduce exactly; T=0 keeps the
exact greedy path, making the fork degenerate (all siblings identical —
useful for verifying page accounting without sampling noise).

Telemetry (docs/OBSERVABILITY.md): ``--metrics-json PATH`` dumps the
paged engine's full metrics snapshot (TTFT / ITL / queue-time
histograms, pool + prefix gauges, per-request timelines);
``--trace-out PATH`` writes the tick journal as Chrome-trace JSON
(load in Perfetto or chrome://tracing); ``--quant-probes`` attaches the
online LO-BCQ activation-quant probes (per-layer/site NMSE + codebook
occupancy) to the W4A4 runtime.  Any of the three implies ``--paged``.

Chaos smoke (docs/ROBUSTNESS.md): ``--chaos`` serves the W4A4 batch
through a paged engine with deterministic fault injection armed at every
seam (``--chaos-seed`` / ``--chaos-rate``), periodic invariant audits
(``--audit-every``), per-request deadlines (``--deadline-s``) and
optional degraded mode (``--degrade-after``), then writes a containment
report (``--chaos-report``) that ``tools/check_chaos.py`` validates:
zero leaked pages, zero unhandled exceptions, clean final audit.
``--host-tier`` (with ``--host-pages N``) adds the host-RAM swap tier to
any paged or chaos run: evicted parked prefix pages and preemption
snapshots demote to a bounded pinned host pool and stream back with
blake2b-verified integrity (a corrupt swap-in quarantines only its
owner); ``--recompress-after N`` arms the cold-page recompression ladder
(bf16→int8→bcq4) under sustained allocator pressure.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, get_smoke
from repro.core import ptq
from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.data.pipeline import DataConfig, batch_at
from repro.models import zoo
from repro.models.layers import Runtime
from repro.serving.generate import (  # noqa: F401 (re-export)
    Request,
    SamplingParams,
    greedy_generate,
)


def _stat(snap: dict, name: str, default=0):
    """Tolerant metric read from an engine snapshot(): counters first,
    then gauges — a renamed or absent metric degrades to ``default``
    instead of raising a KeyError mid-serve."""
    for table in ("counters", "gauges"):
        v = snap.get(table, {}).get(name)
        if v is not None:
            return v
    return default


def _check_servable(api, cfg) -> object:
    """The paged gate: return the family's PageSpec or raise the typed,
    actionable error (names the family AND the supported list) instead of
    failing deep inside an engine constructor."""
    spec = getattr(api, "page_spec", None)
    if spec is None:
        raise zoo.UnsupportedModelError(
            cfg.name, cfg.family,
            reason="Drop --paged/--chaos/--best-of or pick an arch from a "
            "servable family.",
        )
    return spec


def _stub_frames(cfg) -> np.ndarray:
    """Deterministic stub audio-frame embeddings for enc-dec serving (the
    conv frontend is stubbed repo-wide).  ONE frame tensor for the whole
    batch, so the shared-encoder page dedupes every request's encode."""
    return np.asarray(
        jax.random.normal(
            jax.random.PRNGKey(11), (cfg.encoder_len, cfg.d_model)
        ) * 0.02,
        np.float32,
    )


def generate_contiguous(api, cfg, params, prompts, frames, gen_len: int,
                        max_len: int, kv_bucket: int = 0):
    """Contiguous greedy decode for ANY servable family: plain
    ``greedy_generate`` unless the family conditions on frames (enc-dec),
    which the generic prompt-only helper cannot carry."""
    if frames is None:
        return greedy_generate(api, params, prompts, gen_len, max_len,
                               kv_bucket=kv_bucket)
    from repro.serving.generate import next_greedy_tokens

    b, s = prompts.shape
    fr = jnp.broadcast_to(jnp.asarray(frames)[None], (b,) + frames.shape)
    logits, caches = jax.jit(
        lambda p, t, f: api.prefill_fn(p, {"tokens": t, "frames": f}, max_len)
    )(params, prompts, fr)
    out = [next_greedy_tokens(logits)]
    step = jax.jit(api.decode_fn)
    for t in range(gen_len - 1):
        logits, caches = step(params, caches, out[-1][:, None], jnp.int32(s + t))
        out.append(next_greedy_tokens(logits))
    return jnp.stack(out, 1)


def serve_paged(api, params, prompts, gen_len: int, max_len: int, page_size: int,
                chunked: bool = False, prefill_chunk: int = 0, telemetry=None,
                pipeline_depth: int = 2, frames=None, host_pages: int = 0,
                recompress_after: int = 0):
    """Serve the prompt batch through the page-spec'd engine — PagedEngine
    for kv_paged families, StatePagedEngine for state_checkpoint families
    (SSM / hybrid / enc-dec).  ``host_pages > 0`` bounds a host-RAM swap
    tier (evicted parked pages + preemption snapshots demote with
    verified integrity); ``recompress_after > 0`` arms the cold-page
    recompression ladder (kv layout only).  The engines are strict: a
    failing prefill or decode raises instead of finishing its request
    with an error (request-level containment is what ``run_chaos``
    tests).  Returns (tokens, engine)."""
    spec = getattr(api, "page_spec", None)
    if spec is not None and spec.layout == "state_checkpoint":
        from repro.serving.state_engine import StatePagedEngine

        assert not chunked, "state_checkpoint families prefill in one launch"
        engine = StatePagedEngine(
            api, params, n_slots=prompts.shape[0], max_len=max_len,
            page_size=page_size, telemetry=telemetry,
            pipeline_depth=pipeline_depth,
            host_pages=host_pages,
            strict=True,
        )
    else:
        from repro.serving.engine import PagedEngine

        engine = PagedEngine(
            api, params, n_slots=prompts.shape[0], max_len=max_len, page_size=page_size,
            chunked_prefill=chunked,
            prefill_chunk=prefill_chunk or 2 * page_size,
            telemetry=telemetry,
            pipeline_depth=pipeline_depth,
            host_pages=host_pages,
            recompress_after=recompress_after,
            strict=True,
        )
    for i in range(prompts.shape[0]):
        engine.submit(Request(rid=i, prompt=np.asarray(prompts[i]),
                              max_new=gen_len - 1, frames=frames))
    finished, _ = engine.run_to_completion()
    out = {r.rid: r.out for r in finished}
    return jnp.asarray([out[i][:gen_len] for i in range(prompts.shape[0])], jnp.int32), engine


def run_chaos(api, params, prompts, args, max_len: int, frames=None) -> dict:
    """Chaos smoke: a paged engine under deterministic fault injection.

    Two submission waves over a slot-constrained engine (so requests
    queue, preempt, and contend for pages) with every fault site armed
    at ``--chaos-rate``; the run must drain with zero unhandled
    exceptions, zero referenced pages, and a clean final audit.  The
    report JSON is the contract ``tools/check_chaos.py`` validates.
    State-checkpoint families run the same scenario through
    StatePagedEngine (state/shared_ro pages instead of block tables)."""
    from repro.serving.audit import audit_engine
    from repro.serving.faults import SITES, FaultInjector

    batch = int(prompts.shape[0])
    spec = getattr(api, "page_spec", None)
    is_state = spec is not None and spec.layout == "state_checkpoint"
    # transient sites at the full rate; the fatal-per-request sites
    # (logits, sampler — each roll kills a request) at a fifth, so runs
    # keep exercising the healthy path alongside the quarantines
    rates = {
        s: (args.chaos_rate / 5 if s in ("logits", "sampler") else args.chaos_rate)
        for s in SITES
    }
    faults = FaultInjector(seed=args.chaos_seed, rates=rates)
    host_pages = args.host_pages if args.host_tier else 0
    if is_state:
        from repro.serving.state_engine import StatePagedEngine

        engine = StatePagedEngine(
            api, params, n_slots=batch, max_len=max_len,
            page_size=args.page_size,
            fault_injector=faults,
            audit_every=args.audit_every or 4,
            max_queue=2 * batch,
            degrade_after=args.degrade_after,
            pipeline_depth=args.pipeline_depth,
            host_pages=host_pages,
        )
    else:
        from repro.serving.engine import PagedEngine

        engine = PagedEngine(
            api, params, n_slots=batch, max_len=max_len,
            page_size=args.page_size, chunked_prefill=True,
            prefill_chunk=args.prefill_chunk or 2 * args.page_size,
            fault_injector=faults,
            audit_every=args.audit_every or 4,
            max_queue=2 * batch,
            degrade_after=args.degrade_after,
            pipeline_depth=args.pipeline_depth,
            host_pages=host_pages,
            recompress_after=args.recompress_after,
        )
    # two waves: wave 2 queues behind wave 1, so admission, shedding and
    # preemption all see contention; odd rids fork into 2 siblings
    reqs = []
    for wave in range(2):
        for i in range(batch):
            rid = wave * batch + i
            reqs.append(Request(
                rid=rid, prompt=np.asarray(prompts[i]), max_new=args.gen - 1,
                n_samples=2 if rid % 2 else 1,
                deadline_s=args.deadline_s,
                frames=frames,
            ))
    unhandled = None
    ticks = 0
    try:
        for req in reqs:
            engine.submit(req)
        _, ticks = engine.run_to_completion(max_ticks=10_000)
    except Exception as exc:  # the whole point: this must never happen
        unhandled = f"{type(exc).__name__}: {exc}"
    report = audit_engine(engine)
    leaked = int((engine.pool_mgr.refcount > 0).sum())
    outcomes = [
        {
            "rid": int(r.rid),
            "sample_idx": int(r.sample_idx),
            "error_kind": getattr(r.error, "kind", None) if r.error is not None else None,
            "n_out": len(r.out),
        }
        for r in engine.finished
    ]
    finished_rids = {o["rid"] for o in outcomes}
    out = {
        "schema": 1,
        "arch": args.arch,
        "cache": args.cache,
        "page_layout": getattr(engine, "PAGE_LAYOUT", "kv"),
        "host_tier": bool(args.host_tier),
        "host_pages": host_pages,
        "recompress_after": args.recompress_after,
        "chaos_seed": args.chaos_seed,
        "chaos_rate": args.chaos_rate,
        "deadline_s": args.deadline_s,
        "n_requests": len(reqs),
        "all_finished": finished_rids == {r.rid for r in reqs},
        "ticks": ticks,
        "unhandled_exception": unhandled,
        "leaked_pages": leaked,
        # live (allocated or parked) pages per kind after the drain —
        # refcounted pages would be leaks; parked shared_ro/kv prefix
        # pages are retention by design
        "pages_by_kind": engine.pool_mgr.used_by_kind(),
        "final_audit": report.to_dict(),
        "health": engine.health(),
        "faults": faults.summary(),
        "requests": outcomes,
    }
    errs: dict = {}
    for o in outcomes:
        if o["error_kind"]:
            errs[o["error_kind"]] = errs.get(o["error_kind"], 0) + 1
    sw = out["health"].get("swap", {})
    print(
        f"chaos  : seed={args.chaos_seed} rate={args.chaos_rate} "
        f"cache={args.cache} host_tier={'on' if args.host_tier else 'off'} — "
        f"{len(outcomes)} finished over {ticks} ticks, "
        f"{out['faults']['total']} faults injected {out['faults']['by_site']}, "
        f"errors {errs or '{}'}; leaked pages {leaked}, "
        f"audit {'clean' if report.ok else 'DIRTY'}, "
        f"unhandled {unhandled or 'none'}"
    )
    if args.host_tier:
        print(
            f"chaos  : swap outs={sw.get('swap_outs', 0)} "
            f"ins={sw.get('swap_ins', 0)} "
            f"(verified {sw.get('verified_swapins', 0)} / corrupt "
            f"{sw.get('corrupt_swapins', 0)}), skips={sw.get('swap_skips', 0)}, "
            f"bytes={sw.get('swap_bytes', 0)}, "
            f"recompressed={sw.get('recompressed_pages', 0)}"
        )
    if args.chaos_report:
        with open(args.chaos_report, "w") as f:
            json.dump(out, f, indent=1)
        print(f"chaos  : report -> {args.chaos_report}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt3_126m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache", default="bf16", choices=["bf16", "int8", "bcq4"])
    ap.add_argument("--paged", action="store_true", help="serve W4A4 via the paged engine")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="with --paged: chunk-at-a-time admission — prefill runs "
                         "chunk-by-chunk against gathered pages (interleaved with "
                         "decode ticks), prefix-hit pages are read instead of "
                         "recomputed, and prompts may exceed --prompt-len slabs "
                         "(block tables grow; no max_len prefill cap)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill chunk size in tokens (page multiple; "
                         "0 = 2 pages)")
    ap.add_argument("--kv-bucket", type=int, default=0,
                    help="bucketed decode cache reads (0 = full-cache reads)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="paged tick-loop dispatch queue depth: 2 (default) "
                         "enqueues tick t+1's decode launch before syncing "
                         "tick t so host scheduling overlaps device compute; "
                         "1 = legacy synchronous loop (tokens are "
                         "bit-identical either way)")
    ap.add_argument("--packed", action="store_true",
                    help="also serve with packed 4-bit weights (fused kernel path)")
    ap.add_argument("--unfused", action="store_true",
                    help="with --packed: use decode_packed_weight + einsum instead")
    ap.add_argument("--best-of", type=int, default=1,
                    help="fork every prompt into N sampled siblings through "
                         "the paged engine (prompt pages shared by refcount, "
                         "tail pages copy-on-write)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="seeded sampling temperature (0 = exact greedy; "
                         "with --best-of 0 makes the fork degenerate)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the top-k logits only (0 = full vocab)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed — tokens are deterministic per "
                         "(seed, sample index, position)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the paged engine's metrics snapshot "
                         "(histograms / gauges / timelines) as JSON; "
                         "implies --paged")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the tick journal as Chrome-trace JSON "
                         "(Perfetto / chrome://tracing); implies --paged")
    ap.add_argument("--quant-probes", action="store_true",
                    help="attach online LO-BCQ activation-quant probes "
                         "(per-layer/site NMSE + codebook-cluster occupancy) "
                         "to the W4A4 runtime; implies --paged")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos smoke: serve the W4A4 batch through a paged "
                         "engine with deterministic fault injection at every "
                         "seam (serving/faults.py) + periodic invariant "
                         "audits, then report containment (zero leaked "
                         "pages, zero unhandled exceptions, clean final "
                         "audit — validated by tools/check_chaos.py). "
                         "Runs INSTEAD of the serving comparisons.")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-injection seed — faults are a pure function "
                         "of (seed, site, tick, key), so a failing seed "
                         "reproduces bit-for-bit")
    ap.add_argument("--chaos-rate", type=float, default=0.05,
                    help="per-site fault probability per injection point")
    ap.add_argument("--chaos-report", default=None, metavar="PATH",
                    help="write the chaos-run report JSON (fault summary, "
                         "engine health, final audit, per-request outcomes)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline (requests over it "
                         "finish with error kind 'expired')")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the page-ownership invariant audit every N "
                         "engine ticks (0 = only at the end; chaos mode "
                         "defaults to 4)")
    ap.add_argument("--degrade-after", type=int, default=None,
                    help="enter degraded mode (reject forks, shrink the "
                         "prefix LRU) after N consecutive ticks at the "
                         "admission watermark (default: off)")
    ap.add_argument("--host-tier", action="store_true",
                    help="enable the host-RAM swap tier: evicted parked "
                         "prefix pages and preemption snapshots demote to "
                         "a bounded pinned host pool (blake2b-verified "
                         "swap-ins; docs/ROBUSTNESS.md) instead of being "
                         "recomputed")
    ap.add_argument("--host-pages", type=int, default=256,
                    help="host-tier capacity in pages (with --host-tier)")
    ap.add_argument("--recompress-after", type=int, default=0,
                    help="recompress cold HBM pages (bf16->int8->bcq4) "
                         "after N consecutive ticks at/below the admission "
                         "watermark (kv layout; 0 = off)")
    args = ap.parse_args()
    if args.metrics_json or args.trace_out or args.quant_probes:
        args.paged = True

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    bcq_cfg = BCQConfig()
    cbs = default_universal_codebooks(bcq_cfg)
    cb = cbs.as_jnp()

    rt_bf16 = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
    probe_sink = None
    if args.quant_probes:
        from repro.serving.telemetry import QuantProbeSink

        probe_sink = QuantProbeSink(n_layers=cfg.n_layers)
    rt_w4a4 = Runtime(
        quant_mode="fake", bcq_cfg=bcq_cfg, compute_dtype=jnp.float32,
        param_dtype=jnp.float32, cache_kind=args.cache,
        quant_probe=probe_sink,
    )
    api = zoo.build(cfg, rt_bf16)
    api_q = zoo.build(cfg, rt_w4a4)
    params = api.init(jax.random.PRNGKey(0))

    # paged-serving gate: typed, actionable rejection BEFORE any compute
    # (e.g. pixtral_12b: the vlm family has no paged path yet)
    needs_paged = args.paged or args.chaos or args.best_of > 1
    spec = _check_servable(api_q, cfg) if needs_paged else getattr(
        api_q, "page_spec", None)
    is_state = spec is not None and spec.layout == "state_checkpoint"
    frames = _stub_frames(cfg) if cfg.family == "encdec" else None

    # --- PTQ: quantize GEMM weights offline with the frozen codebooks ----
    params_q = ptq.quantize_params(params, cb, bcq_cfg)
    params_q["codebooks"] = cb
    stats = ptq.count_quantized_bits(params, bcq_cfg)
    print(
        f"arch={cfg.name} params={stats['params']/1e6:.1f}M "
        f"PTQ compression {stats['compression']:.2f}× "
        f"({bcq_cfg.bitwidth():.3f} bits/GEMM-weight)"
    )

    prompts = batch_at(
        DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len, global_batch=args.batch),
        0,
    )["tokens"]
    max_len = args.prompt_len + args.gen + 1
    if (args.paged or args.chaos or args.best_of > 1) and max_len % args.page_size:
        max_len += args.page_size - max_len % args.page_size

    if args.chaos:
        # chaos smoke REPLACES the serving comparisons: one W4A4 paged
        # engine with every fault seam armed (docs/ROBUSTNESS.md);
        # tools/check_chaos.py validates the report artifact
        run_chaos(api_q, params_q, prompts, args, max_len, frames=frames)
        return None

    t0 = time.time()
    ref = generate_contiguous(api, cfg, params, prompts, frames, args.gen, max_len)
    t_ref = time.time() - t0
    t0 = time.time()
    got = generate_contiguous(api_q, cfg, params_q, prompts, frames, args.gen,
                              max_len, kv_bucket=args.kv_bucket)
    t_q = time.time() - t0

    agree = float(jnp.mean((ref == got).astype(jnp.float32)))
    toks = args.batch * args.gen
    dev = jax.devices()[0]
    print(f"timings: wall clock incl. compile on {dev.platform} ({dev.device_kind})")
    print(f"bf16   : {toks/t_ref:8.1f} tok/s")
    print(f"W4A4   : {toks/t_q:8.1f} tok/s (fake-quant path, cache={args.cache})")
    print(f"greedy token agreement W4A4 vs bf16: {agree*100:.1f}%")

    if args.packed:
        # true-storage serving: packed 4-bit weight buffers end-to-end,
        # linears dispatched to the fused quantize→decode→GEMM kernel
        rt_pk = dataclasses.replace(
            rt_w4a4, quant_mode="packed", fused_linear=not args.unfused
        )
        api_pk = zoo.build(cfg, rt_pk)
        params_pk = ptq.pack_params(params, cb, bcq_cfg)
        params_pk["codebooks"] = cb
        t0 = time.time()
        got_pk = generate_contiguous(api_pk, cfg, params_pk, prompts, frames,
                                     args.gen, max_len)
        t_pk = time.time() - t0
        agree_pk = float(jnp.mean((got_pk == ref).astype(jnp.float32)))
        print(
            f"packed : {toks/t_pk:8.1f} tok/s "
            f"({'fused w4a4_linear kernel' if not args.unfused else 'decode+einsum'}, "
            f"4-bit weight buffers) agreement vs bf16: {agree_pk*100:.1f}%"
        )

    if args.paged and is_state:
        # state-checkpoint families: the paged reference is the fused
        # contiguous decode above (same decode batch once all requests
        # are resident; prefill is per-request, so under fake W4A4 the
        # activation s_X extent differs — agreement is reported, and
        # bit-exactness is asserted with batch-invariant math in
        # tests/test_state_paged.py)
        t0 = time.time()
        got_paged, engine = serve_paged(
            api_q, params_q, prompts, args.gen, max_len, args.page_size,
            pipeline_depth=args.pipeline_depth, frames=frames,
            host_pages=args.host_pages if args.host_tier else 0,
        )
        t_p = time.time() - t0
        agree_p = float(jnp.mean((got_paged == got).astype(jnp.float32)))
        snap = engine.snapshot()
        print(
            f"paged  : {toks/t_p:8.1f} tok/s (state-checkpoint layout, "
            f"page={args.page_size}, "
            f"pages used {_stat(snap, 'pool_peak_pages', 'n/a')}, "
            f"kinds {engine.pool_mgr.used_by_kind()}, "
            f"checkpoints {_stat(snap, 'state_checkpoints')}, "
            f"enc prefix hits {_stat(snap, 'prefix_hits')}) "
            f"agreement vs contiguous {agree_p*100:.1f}%"
        )
    elif args.paged:
        # engine-vs-engine comparison (same per-request prefill and tick
        # batch composition; the fused greedy_generate above quantizes
        # activations over a different batch, so it is not the reference)
        from repro.launch.batching import ContinuousBatcher

        t0 = time.time()
        cbat = ContinuousBatcher(api_q, params_q, n_slots=args.batch, max_len=max_len)
        for i in range(args.batch):
            cbat.submit(Request(rid=i, prompt=np.asarray(prompts[i]), max_new=args.gen - 1))
        fin_c, _ = cbat.run_to_completion()
        t_c = time.time() - t0
        t0 = time.time()
        got_paged, engine = serve_paged(
            api_q, params_q, prompts, args.gen, max_len, args.page_size,
            pipeline_depth=args.pipeline_depth,
            host_pages=args.host_pages if args.host_tier else 0,
            recompress_after=args.recompress_after,
        )
        t_p = time.time() - t0
        out_c = {r.rid: r.out for r in fin_c}
        ref_c = jnp.asarray([out_c[i][: args.gen] for i in range(args.batch)], jnp.int32)
        match = bool(jnp.all(got_paged == ref_c))
        snap = engine.snapshot()
        print(f"contig : {toks/t_c:8.1f} tok/s (slot-contiguous engine)")
        print(
            f"paged  : {toks/t_p:8.1f} tok/s (page={args.page_size}, "
            f"pages used {_stat(snap, 'pool_peak_pages', 'n/a')}, "
            f"prefix hits {_stat(snap, 'prefix_hits')}) "
            f"outputs {'==' if match else '!='} contiguous engine"
        )
        if args.chunked_prefill:
            # NOTE: under fake W4A4 the dynamic per-tensor activation s_X
            # sees chunk-sized prefill batches, so tokens may drift from the
            # full-prefill engines (quantizer batch extent, not a serving
            # bug) — chunked vs non-chunked is bit-exact per cache kind when
            # the model math is batch-invariant (tests/test_chunked_prefill).
            t0 = time.time()
            got_ck, eng_ck = serve_paged(
                api_q, params_q, prompts, args.gen, max_len, args.page_size,
                chunked=True, prefill_chunk=args.prefill_chunk,
                pipeline_depth=args.pipeline_depth,
            )
            t_ck = time.time() - t0
            agree_ck = float(jnp.mean((got_ck == ref_c).astype(jnp.float32)))
            snap_ck = eng_ck.snapshot()
            print(
                f"chunked: {toks/t_ck:8.1f} tok/s (prefill chunk="
                f"{args.prefill_chunk or 2 * args.page_size}, "
                f"{_stat(snap_ck, 'prefill_chunks')} chunks, "
                f"prefill tokens {_stat(snap_ck, 'prefill_tokens')} run / "
                f"{_stat(snap_ck, 'prefill_tokens_skipped')} prefix-skipped) "
                f"agreement vs contiguous {agree_ck*100:.1f}% "
                "(W4A4 act s_X sees chunk-sized batches)"
            )

    if args.paged and (args.metrics_json or args.trace_out or args.quant_probes):
        # telemetry artifacts come from the richest engine run above
        # (chunked if it ran — its journal has per-chunk prefill spans)
        src = eng_ck if (args.chunked_prefill and not is_state) else engine
        tel = src.telemetry
        if args.metrics_json:
            tel.dump_metrics(args.metrics_json, engine=src, probe_sink=probe_sink)
            print(f"telemetry: metrics snapshot -> {args.metrics_json}")
        if args.trace_out:
            tel.dump_trace(args.trace_out)
            print(f"telemetry: Chrome trace ({len(tel.journal)} events, "
                  f"{tel.journal.dropped} dropped) -> {args.trace_out}")
        hs = tel.registry.snapshot()["histograms"]
        ttft, itl, qt = hs["ttft_s"], hs["itl_s"], hs["queue_time_s"]
        print(
            f"telemetry: ttft mean {ttft['mean']*1e3:.2f} ms (n={ttft['count']}), "
            f"itl mean {itl['mean']*1e3:.2f} ms (n={itl['count']}), "
            f"queue mean {qt['mean']*1e3:.2f} ms (n={qt['count']})"
        )
        if probe_sink is not None:
            rep = probe_sink.report()
            worst = sorted(
                (
                    (d["nmse_mean"], site, layer)
                    for site, per in rep["sites"].items()
                    for layer, d in per.items()
                ),
                reverse=True,
            )[:3]
            print(
                f"quant-probes: {rep['emissions']} emissions over "
                f"{len(rep['sites'])} sites × {rep['n_layers']} layers; "
                "worst NMSE: "
                + ", ".join(f"{s}/L{l}={m:.2e}" for m, s, l in worst)
            )

    if args.best_of > 1:
        # sequence forking: each prompt prefills ONCE, then forks into
        # --best-of sibling decode branches sharing every prompt page by
        # refcount (kv layout: COW-divergent tail pages; state layout:
        # live-row copies sharing the checkpoint/encoder pages)
        sp = SamplingParams(
            temperature=args.temperature, top_k=args.top_k, seed=args.seed
        )
        if is_state:
            from repro.serving.state_engine import StatePagedEngine

            eng_f = StatePagedEngine(
                api_q, params_q, n_slots=args.batch * args.best_of,
                max_len=max_len, page_size=args.page_size,
                pipeline_depth=args.pipeline_depth,
            )
        else:
            from repro.serving.engine import PagedEngine

            eng_f = PagedEngine(
                api_q, params_q, n_slots=args.batch * args.best_of,
                max_len=max_len, page_size=args.page_size,
                pipeline_depth=args.pipeline_depth,
            )
        t0 = time.time()
        for i in range(args.batch):
            eng_f.submit(Request(
                rid=i, prompt=np.asarray(prompts[i]), max_new=args.gen - 1,
                n_samples=args.best_of, sampling=sp, frames=frames,
            ))
        fin_f, _ = eng_f.run_to_completion()
        t_f = time.time() - t0
        by_rid: dict = {}
        for r in fin_f:
            by_rid.setdefault(r.rid, {})[r.sample_idx] = r.out
        s = eng_f.snapshot()
        print(
            f"best-of: {args.batch * args.best_of * args.gen / t_f:8.1f} tok/s "
            f"({args.best_of} forked samples/prompt, T={args.temperature}, "
            f"seed={args.seed}) — forks {_stat(s, 'forks')}, shared pages "
            f"{_stat(s, 'shared_pages')}, COW copies {_stat(s, 'cow_copies')}, "
            f"peak pages {_stat(s, 'pool_peak_pages', 'n/a')} "
            f"(n-independent would prefill {args.best_of}× and share nothing)"
        )
        if args.temperature == 0 and args.paged:
            # degenerate fork: every sibling must replay the paged greedy row
            exact = all(
                by_rid[i][k][: args.gen] == [int(t) for t in got_paged[i]]
                for i in range(args.batch) for k in by_rid[i]
            )
            print(f"best-of @ T=0: siblings {'==' if exact else '!='} unforked greedy")
        for k in sorted(by_rid.get(0, {})):
            print(f"  rid0 sample{k}:", by_rid[0][k][:10])

    print("sample bf16:", np.asarray(ref[0][:10]))
    print("sample w4a4:", np.asarray(got[0][:10]))
    return agree


if __name__ == "__main__":
    main()
