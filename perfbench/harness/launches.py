"""What the window's launches processed, for the per-layer readers: the
live rows of each launch (from the engine's launch spans), the rows of
each chunk launch (from the request timelines: chunk start and length),
and the context of every generated token (from the tokens the client
saw).  All restricted to the measured window."""
from __future__ import annotations

from collections import defaultdict


def _window(ctx):
    return ctx["open"][0], ctx["close"][0]


def linear_rows(ctx) -> list[int]:
    """Live rows of every decode and chunk launch in the window."""
    o, c = _window(ctx)
    rows = []
    for kind, name, _cat, _tid, t0, _t1, args, _seq in ctx["journal"]:
        if kind != "span" or not (o <= t0 < c):
            continue
        if name == "decode_tick":
            rows.append(int(args["n_active"]))
        elif name == "prefill_launch":
            rows.append(int(args["tokens"]))
    return rows


def chunks(ctx) -> dict:
    """launch end time -> [(n_past, n_tokens, prompt_len)] of each chunk
    that a launch ending in the window advanced."""
    o, c = _window(ctx)
    plen = {t.arrival.rid: len(t.arrival.prompt) for t in ctx["run"].tracked}
    out = defaultdict(list)
    for tl in ctx["timelines"]:
        done = 0
        for t1, n in tl.chunks:
            if o <= t1 < c:
                out[t1].append((done, n, plen.get(tl.rid, 0)))
            done += n
    return out


def decode_contexts(ctx) -> list[int]:
    """Context (tokens already cached) of every generated token that
    became visible in the window, the first token of each request (which
    its last prompt chunk yields) excluded."""
    o, c = _window(ctx)
    out = []
    for t in ctx["run"].tracked:
        p = len(t.arrival.prompt)
        out += [p + i - 1 for i, s in enumerate(t.times) if i >= 1 and o <= s < c]
    return out


def attention_rows(ctx) -> list[list[tuple[int, int]]]:
    """(n_past, n_queries) rows grouped by launch: one group per chunk
    launch, and each generated token on its own (decode attention is
    bound by its bytes in every launch, so grouping decode rows does not
    change the least time)."""
    groups = [[(n_past, n) for n_past, n, _ in rows] for rows in chunks(ctx).values()]
    return groups + [[(ctx_len, 1)] for ctx_len in decode_contexts(ctx)]


def token_contexts(ctx) -> list[tuple[int, bool]]:
    """(context, yields logits) of every token the window processed."""
    out = []
    for rows in chunks(ctx).values():
        for n_past, n, p in rows:
            out += [(n_past + j, n_past + j == p - 1) for j in range(n)]
    return out + [(n, True) for n in decode_contexts(ctx)]
