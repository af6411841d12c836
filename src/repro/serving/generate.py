"""Shared decoding helpers: the greedy loop / stop rule and seeded
temperature sampling.

One implementation of the decode-token choice, used by the single-batch
driver (launch/serve.py, examples), the contiguous continuous-batching
engine (launch/batching.py) and the paged engine (serving/engine.py) —
previously copy-pasted per call-site.

**Sampling determinism contract** (``SamplingParams`` + ``sample_token``):
the PRNG key for a token depends ONLY on ``(seed, sample_idx, absolute
position)`` — the sampled token's own sequence index, i.e. the number of
tokens (prompt + generated) that precede it — never on batch
composition, slot index, or tick count.  That
makes sampled runs (a) reproducible across processes, (b) identical for a
sequence whether it decodes alone or fused with others, and (c) exact
under preemption-by-eviction: a recompute-requeued sequence replays its
prompt + generated tokens and then resamples position p with the very key
that produced it the first time.  ``temperature == 0`` bypasses sampling
entirely and takes the argmax path, so greedy serving stays bit-identical
to the pre-sampling engines.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode-sampling policy (frozen — safe to share across
    forked siblings).  ``temperature == 0`` means exact greedy argmax;
    ``top_k == 0`` means the full vocabulary."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


class RequestError(str):
    """Typed terminal error of a Request.

    A ``str`` subclass so every existing caller that treats
    ``req.error`` as a message (``"..." in req.error``, printing,
    ``is not None`` checks) keeps working, while new callers branch on
    ``req.error.kind``:

    * ``"invalid"``     — rejected at submit() (bad n_samples, ...)
    * ``"too_long"``    — non-chunked slab cannot hold the prompt
    * ``"cancelled"``   — ``Request.cancel()`` honored by the engine
    * ``"expired"``     — deadline_s exceeded, or the output stalled
                          longer than max_output_stall_ticks
    * ``"shed"``        — dropped by load shedding (full admission
                          queue, unserveable head-of-line request, or
                          degraded-mode fork rejection)
    * ``"quarantined"`` — a fault (NaN logits, sampler/state exception)
                          was contained to this request mid-tick
    """

    __slots__ = ("kind",)

    def __new__(cls, kind: str, msg: str):
        obj = super().__new__(cls, msg)
        obj.kind = kind
        return obj

    def __repr__(self):
        return f"RequestError({self.kind!r}, {str(self)!r})"


@dataclasses.dataclass
class Request:
    """One serving request (shared by the contiguous and paged engines).

    ``n_samples > 1`` asks the paged engine to FORK the sequence after
    prefill into that many siblings (best-of-n / parallel sampling), each
    sharing every prompt page by refcount and recorded in ``finished`` as
    its own Request with this ``rid`` and a distinct ``sample_idx``.
    The submitted object itself becomes sibling 0 (n_samples demoted to
    1 at fork time), so ``done``/``out`` polling works unchanged.
    ``error`` marks a request the engine finished abnormally (a
    :class:`RequestError`, or a plain string from older call sites) — it
    lands in ``finished`` instead of poisoning the serving loop.

    **Lifecycle guard** (paged engine): ``deadline_s`` bounds the
    elapsed time from ORIGINAL submission to finish, measured on the
    monotonic ``time.perf_counter()`` clock (the engine's only clock —
    immune to wall-clock steps from NTP/DST; not comparable to
    ``time.time()`` values).  The anchor is stamped once at submit()
    and carried verbatim through every preemption/resubmission cycle,
    so a preempted-and-resumed request keeps spending the SAME budget
    (tested in tests/test_pipelined_engine.py).  An over-deadline
    request is torn down (every page ref and fork reservation released)
    with ``error.kind == "expired"`` wherever it is: queued,
    prefilling, or decoding.  ``max_output_stall_ticks`` bounds how many engine ticks
    may pass without this request emitting a token (preemption
    starvation guard).  ``cancel()`` requests asynchronous teardown,
    honored at the next tick boundary with ``error.kind == "cancelled"``.
    Both deadlines and the stall clock survive preemption (the resumed
    request keeps the original submit anchor)."""

    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    sampling: SamplingParams = GREEDY
    n_samples: int = 1
    sample_idx: int = 0
    error: Optional[str] = None
    # non-token conditioning for shared-encoder families (enc-dec): stub
    # frame embeddings (T_enc, D).  The state engine keys its read-only
    # encoder page on these bytes, so identical frames across requests
    # share one encode; carried verbatim through preemption/resubmission.
    frames: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # keep_prompt_logits=True: the paged engines store the submitted
    # prompt's last-position logits (what the first token is picked from)
    # in ``prompt_logits`` as a host float32 (V,) array — for comparing
    # one serving route against another on the same requests
    keep_prompt_logits: bool = False
    prompt_logits: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # --- lifecycle guard (None = unbounded) ---
    deadline_s: Optional[float] = None
    max_output_stall_ticks: Optional[int] = None
    cancelled: bool = False
    # telemetry lifecycle timeline (serving.telemetry.RequestTimeline) —
    # attached at submit(), carried through preemption/resubmission so the
    # resumed request keeps its original submit timestamp (TTFT spans the
    # preemption); None when telemetry runs at counters-only level
    timeline: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # engine-private memo: (page_size, chunk_hashes(prompt)) — a request
    # blocked at the admission watermark is re-planned every tick and must
    # not re-digest its whole (immutable) prompt each time
    _hash_cache: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # engine-private lifecycle anchors: monotonic (time.perf_counter)
    # submit timestamp — deadlines span preemptions, the resumed request
    # carries it over verbatim — and the engine tick of the last emitted
    # token (stall guard)
    _t_submit: Optional[float] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _progress_tick: int = dataclasses.field(default=0, repr=False, compare=False)
    # transient-admission-failure retry budget (fault containment)
    _admit_retries: int = dataclasses.field(default=0, repr=False, compare=False)
    # length of the prompt the CALLER submitted.  A preemption requeue
    # folds generated tokens into the prompt (prompt := prompt + out); a
    # SECOND preemption must append only the output suffix generated
    # since, or the folded tokens double-count (wrong KV, shifted sample
    # positions).  None = nothing folded yet (len(prompt) is original).
    _orig_plen: Optional[int] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # preemption resume chain: the engine requeues a preempted request as
    # a NEW Request (prompt := prompt + generated); cancel() walks this
    # link so cancelling the handle the caller submitted still lands
    _resumed_as: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def cancel(self) -> None:
        """Ask the engine to tear this request down.  Safe to call from
        outside the tick loop at any lifecycle stage; the engine honors
        it at the next tick boundary, releasing every page reference and
        fork reservation and finishing the request with
        ``error.kind == "cancelled"``.  Follows the preemption resume
        chain, so the handle the caller submitted keeps working after the
        engine requeued the request in recompute mode.  A no-op once the
        request is done."""
        r = self
        while r is not None:
            r.cancelled = True
            r = r._resumed_as


def api_jit(api, key, fn):
    """jit ``fn`` once per (api, key), with a trace counter.

    Device-step callables are cached PER ModelAPI (not per engine): every
    engine built over the same api shares one compilation per shape
    bucket, so a warmup engine genuinely warms the serving engine and N
    engine instances stop recompiling N times.  Each cached entry is
    ``(jitted_fn, {"traces": n})`` — the wrapped python body runs once per
    jit trace, which is the measurable contract behind the serving-shape
    bucketing policy (see ``PagedEngine.trace_counts``).  The jitted
    function is named after ``key[0]`` (``paged_decode_fused``,
    ``chunk_step``, ``prefill``, ...), so each step program carries its
    key's name in a profile (``jit_paged_decode_fused(...)``)."""
    cache = getattr(api, "_engine_jit_cache", None)
    if cache is None:
        cache = {}
        api._engine_jit_cache = cache
    if key not in cache:
        counts = {"traces": 0}

        def counted(*args, _fn=fn, _c=counts):
            _c["traces"] += 1  # python body runs once per jit trace
            return _fn(*args)

        counted.__name__ = counted.__qualname__ = str(key[0])
        cache[key] = (jax.jit(counted), counts)
    return cache[key]


def next_greedy_tokens(logits) -> jnp.ndarray:
    """(B, S, V) logits → (B,) greedy next token at the last position."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _sample_row(logits_row, key, temperature, top_k):
    x = logits_row.astype(jnp.float32) / temperature
    if top_k:
        kth = jax.lax.top_k(x, min(top_k, x.shape[-1]))[0][..., -1]
        x = jnp.where(x < kth, -jnp.inf, x)
    return jax.random.categorical(key, x)


def sampling_key(sp: SamplingParams, sample_idx: int, pos: int) -> jax.Array:
    """The deterministic per-token key: fold (sample_idx, position) into
    the request seed.  See the module docstring for why position-keying
    (not tick-keying) is load-bearing for preemption exactness."""
    key = jax.random.PRNGKey(sp.seed)
    return jax.random.fold_in(jax.random.fold_in(key, sample_idx), pos)


def sample_token(logits_row, sp: SamplingParams, sample_idx: int, pos: int) -> int:
    """Seeded temperature/top-k sample of ONE sequence's next token.

    logits_row: (V,) last-position logits for this sequence.  Requires
    ``sp.temperature > 0`` (greedy requests never reach the sampler)."""
    assert sp.temperature > 0.0, "greedy requests take the argmax path"
    key = sampling_key(sp, sample_idx, pos)
    return int(
        _sample_row(jnp.asarray(logits_row), key, jnp.float32(sp.temperature), sp.top_k)
    )


def pick_token(logits_row, greedy_tok: int, req: Request, pos: int) -> int:
    """The shared token choice: exact argmax for greedy requests (the
    batched ``next_greedy_tokens`` result passes through untouched, so
    greedy serving is bit-identical to the pre-sampling engines), seeded
    sampling otherwise."""
    if req.sampling.greedy:
        return greedy_tok
    return sample_token(logits_row, req.sampling, req.sample_idx, pos)


def sequence_finished(tok: int, n_out: int, max_new: int, pos: int, max_len: int,
                      eos_id: int = -1) -> bool:
    """Stop rule shared by every serving path: EOS, generation budget
    (prefill token + max_new decode tokens), or cache exhaustion."""
    return tok == eos_id or n_out >= max_new + 1 or pos >= max_len - 1


def kv_bucket_bound(n_valid: int, bucket: int, max_len: int) -> int:
    """Round the live-token count up to a bucket multiple (static per
    compilation), capped at the cache length."""
    return min(max_len, -(-n_valid // bucket) * bucket)


def greedy_generate(api, params, prompts, gen_len: int, max_len: int,
                    kv_bucket: int = 0):
    """Batched greedy decoding: prefill the prompt batch, then ``gen_len``
    fused decode steps.  Returns (B, gen_len) int32 tokens.

    ``kv_bucket`` > 0 bounds each decode step's cache read to the written
    prefix rounded up to a bucket multiple (one retrace per bucket), so
    int8/bcq4 dequantization stops paying for unwritten positions.  Only
    attention-cache families accept the bound."""
    b, s = prompts.shape
    logits, caches = jax.jit(lambda p, t: api.prefill_fn(p, {"tokens": t}, max_len))(
        params, prompts
    )
    out = [next_greedy_tokens(logits)]
    if kv_bucket:
        step = jax.jit(
            lambda p, c, t, pos, kb: api.decode_fn(p, c, t, pos, kv_bound=kb),
            static_argnums=(4,),
        )
    else:
        step = jax.jit(api.decode_fn)
    for t in range(gen_len - 1):
        pos = s + t
        if kv_bucket:
            kb = kv_bucket_bound(pos + 1, kv_bucket, max_len)
            logits, caches = step(params, caches, out[-1][:, None], jnp.int32(pos), kb)
        else:
            logits, caches = step(params, caches, out[-1][:, None], jnp.int32(pos))
        out.append(next_greedy_tokens(logits))
    return jnp.stack(out, 1)
