"""Device time per execution of the jitted decode step: every execution
of the ``jit_decode_step`` program that runs under ``ServeEngine.decode``
(the prefill programs, one per prompt length, run under ``prefill``)."""


def read(ctx):
    d = ctx.trace.module_time("jit_decode_step", "engine.decode")
    return sum(d) / len(d) * 1e3 if d else None
