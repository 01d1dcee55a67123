"""Model FLOPs utilisation of the whole served step: the model FLOPs of
every prompt and output token processed in the traced window (two per
matmul parameter, plus attention up to each position), over the window
times the chip's peak bf16 FLOP/s."""


def read(ctx):
    fam, conf = ctx.family, ctx.conf
    calls = [c for c in ctx.spans.calls if c[2] in ("prefill", "decode")]
    if not calls:
        return None
    flops = sum(fam.span_flops(conf, pos, n) for _, _, _, pos, n in calls)
    return flops / (ctx.window_s * ctx.peak["bf16_flop_s"]) * 100
