"""Model step (``models/transformer.py``): model FLOPs of the tokens the
window processed (prompt tokens of its chunk launches, generated tokens
of its decode launches: 2 per weight of the linears, the LM head where a
token yields logits, attention over the live context), over the window's
seconds times the chip's int8 peak.  Moves ``ttft_p95_s``."""
from harness import launches, work


def read(ctx):
    cfg, t = ctx["cfg"], ctx["trace"]["window_s"]
    if t <= 0:
        return None
    flops = sum(work.token_flops(cfg, n, head) for n, head in launches.token_contexts(ctx))
    return 100.0 * flops / (t * ctx["peaks"]["int8_ops_per_s"])
