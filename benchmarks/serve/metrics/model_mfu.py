"""Model FLOPs utilisation of the traced slice: the FLOPs every prefill
and decode call in it must do (``servebench.work``: a prefill's output
head once, for the row whose logits are kept), over the slice times the
chip's peak bf16 FLOP/s."""

from servebench import work


def read(ctx):
    calls = [c for c in ctx.spans.calls if c[2] in ("prefill", "decode")]
    if not calls:
        return None
    flops = sum(work.needed_flops(ctx.family, ctx.conf, pos, n)
                for _, _, _, pos, n in calls)
    return flops / (ctx.window_s * ctx.peak["bf16_flop_s"]) * 100
