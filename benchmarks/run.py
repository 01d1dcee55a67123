"""Benchmark driver: one module per paper table/figure + roofline.

Prints ``table,name,value,unit,note`` CSV rows.  Run with
``PYTHONPATH=src python -m benchmarks.run`` (optionally ``--only fig15``).
With ``--json-dir DIR`` (or ``BENCH_JSON_DIR`` in the environment) each
module additionally writes its rows as a ``BENCH_<module>.json``
artifact — the per-PR perf trajectory CI uploads.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache

from .common import ROWS, dump_json

MODULES = [
    "table1_direct",
    "table2_policy",
    "table4_weights",
    "fig12_14_throughput",
    "fig15_kv_ratio",
    "fig16_planes",
    "fig18_21_dram",
    "table5_ppa",
    "kernels_bench",
    "decode_microbench",
    "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module filter")
    ap.add_argument("--json-dir", default=None,
                    help="also write per-module BENCH_<module>.json "
                         "artifacts here (defaults to $BENCH_JSON_DIR; "
                         "unset = CSV only)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    print("table,name,value,unit,note")
    failures = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        mark = len(ROWS)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            dt = time.perf_counter() - t0
            path = dump_json(name, first_row=mark, duration_s=dt,
                             out_dir=args.json_dir)
            print(f"# {name} done in {dt:.1f}s"
                  + (f" → {path}" if path else ""),
                  file=sys.stderr, flush=True)
        # tracecheck: allow-broad-except(one failing benchmark is reported at exit; the rest of the suite still runs)
        except Exception as e:  # keep the suite running
            failures.append((name, repr(e)))
            print(f"# {name} FAILED: {e!r}", file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
