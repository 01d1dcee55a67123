"""One run of one cell: set-up, the measured window, the per-layer
readings of a traced run, and the check against the reference."""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

from . import correct, peaks
from .loop import ClosedLoop
from .spans import CALLS, SPANS, Spans
from .traffic import Traffic

#: Seconds at the end of the window that a traced run profiles.  Stopping
#: the profiler takes seconds per traced second, so the trace covers a
#: slice of the window and is stopped after the window has closed.
TRACE_SECONDS = 8.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class PerLayer:
    """What a per-layer reader (``metrics/<name>.py``) is given."""

    cell: object
    conf: dict
    family: object
    kind: str                 # device_kind
    peak: dict
    window_s: float           # the traced slice of the window, host clock
    spans: Spans
    trace: object             # tracefile.Reduced


def _policy(name: str):
    """``"lossless"`` -> ``paging.LOSSLESS_POLICY``, and so on."""
    from repro.runtime import paging

    return getattr(paging, f"{name.upper()}_POLICY")


def build(cell, seed: int):
    """The served program for this cell and seed: weights, scheduler,
    traffic and the closed loop around them."""
    import jax

    from repro.core.tier import make_device
    from repro.runtime.serving import ServeScheduler

    fam, conf, serve = cell.family, cell.conf, cell.mix["serve"]
    acfg = fam.arch_config(conf)
    params = jax.block_until_ready(fam.make_params(acfg, seed))
    pt = int(serve["page_tokens"])
    traffic = Traffic(cell.mix, acfg.vocab, seed, pt)
    probe = correct.probe_ids(acfg.vocab, seed)
    budget = (int(serve["hbm_kv_bytes"]) if "hbm_kv_bytes" in serve
              else int(serve["hbm_kv_tokens"]) * fam.kv_bytes_per_token(conf))
    sched = ServeScheduler(
        acfg, params, max_batch=traffic.clients,
        device_kind=make_device(serve.get("tier", "trace"), shards=1),
        policy=_policy(serve.get("policy", "lossless")), batch=1,
        page_tokens=pt, hbm_kv_budget=budget,
        max_seq=traffic.max_prompt + traffic.max_output + pt,
        **serve.get("scheduler", {}))
    return params, traffic, ClosedLoop(sched, traffic, probe)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        control: bool = False) -> dict:
    """One run; returns the result line's object."""
    import jax

    # JAX reports a program's compile event for a persistent-cache hit
    # too; the hits are counted apart and taken off.
    compiles, hits = [], []

    def on_duration(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    kind = devs[0].device_kind
    spans = Spans()
    if trace:
        spans.install()
    try:
        params, traffic, loop = build(cell, seed)
        loop.start()
        while not loop.warmed:
            loop.step()
        t_open = loop.ticks[-1]
        setup_s = t_open - t_start
        n_open, h_open = len(compiles), len(hits)
        log(f"[window] opens after {len(loop.ticks)} pre-roll ticks; "
            f"set-up {setup_s:.3f} s, {n_open - h_open} compilations, "
            f"{h_open} programs loaded from the cache")
        jax.config.update("jax_log_compiles", True)   # names any in the window

        t_trace = t_open + max(seconds - TRACE_SECONDS, 0.0) if trace else math.inf
        tdir = ann = None
        t_slice = t_open
        while True:
            t = loop.step()
            if ann is None and t >= t_trace:
                tdir = tempfile.mkdtemp(prefix="servebench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(tdir, profiler_options=opts)
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                t_slice = time.perf_counter()
                spans.on = True
            if t >= t_open + seconds:
                break
        t_close = t
        if ann is not None:
            ann.__exit__(None, None, None)
            spans.on = False
        jax.config.update("jax_log_compiles", False)
        loaded = len(hits) - h_open
        in_window = len(compiles) - n_open - loaded
        log(f"[window] {t_close - t_open:.3f} s, {len(loop.ticks)} ticks in "
            f"all, {in_window} compilations inside the window, {loaded} "
            f"programs loaded from the cache")
        stats = [d.memory_stats() or {} for d in devs[: cell.chips]]
        mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
        e2e = loop.end_to_end(t_open, t_close)
        log(f"[window] {e2e['tokens']} tokens, {e2e['ttft_samples']} first "
            f"tokens, {e2e['gap_samples']} gaps")
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": mem}
        metrics, breakdown = {}, None
        if trace:
            jax.profiler.stop_trace()
            from . import tracefile

            path = next(Path(tdir).rglob("*.xplane.pb"))
            red = tracefile.reduce(path, [s[3] for s in SPANS], CALLS)
            shutil.rmtree(tdir, ignore_errors=True)
            ctx = PerLayer(cell, cell.conf, cell.family, kind, peaks.peak(kind),
                           t_close - t_slice, spans, red)
            for m in cell.per_layer():
                v = cell.reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            breakdown = {"device_ops": red.device_ops(),
                         "idle_gaps": red.gaps()}
        else:
            e2e["setup_s"] = setup_s
            for m in cell.end_to_end():
                if e2e.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        attempted = loop.attempted(t_open, t_close)
        finished = loop.finished_in(t_open, t_close)
        failed = sum(tr.tokens.size != tr.n_out for tr in finished)
        check = dict(cell.conf.get("check", {}), **cell.mix.get("check", {}))
        chosen = correct.sample(finished, int(check["sample_requests"]), seed)
        probe = loop.probe_ids
    finally:
        spans.uninstall()
    # the program's state goes before the reference runs
    loop.sched = None
    del loop
    gc.collect()
    r = correct.read(cell.family, cell.conf, params, chosen,
                     traffic.max_output, probe, control)
    n_min = int(check["min_tokens"])
    result_check = {
        "logit_rmse": {"value": r.rmse(r.err), "limit": check["logit_rmse"]},
        "max_logit_gap": {"value": r.widest(r.gap),
                          "limit": check["max_logit_gap"]},
        "tokens_compared": {"value": int(r.gap.size), "limit": n_min},
        "failed_requests": {"value": int(failed), "limit": 0},
    }
    ok = (result_check["logit_rmse"]["value"] <= check["logit_rmse"]
          and result_check["max_logit_gap"]["value"] <= check["max_logit_gap"]
          and r.gap.size >= n_min and failed == 0)
    if control:       # the readings the limits are set from, not compared
        result_check["control_logit_rmse"] = {
            "value": r.rmse(r.control_err), "limit": check["logit_rmse"]}
        result_check["control_max_logit_gap"] = {
            "value": r.widest(r.control_gap), "limit": check["max_logit_gap"]}
        result_check["fault_max_logit_gap"] = {
            "value": r.widest(r.fault_gap), "limit": check["max_logit_gap"]}
    out = {"correct": bool(ok), "attempted": len(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = result_check
    for name, v in result_check.items():
        log(f"[check] {name} {v['value']!r} limit {v['limit']!r}")
    return out
