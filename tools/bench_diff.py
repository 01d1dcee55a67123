"""Benchmark regression gate: fresh BENCH_*.json vs committed baselines.

CI used to hard-code perf thresholds inline in the benchmark modules
(e.g. kernels_bench's old ``batched >= scalar`` SystemExit) — binary
checks that miss slow drift and rot as workloads change.  This tool
replaces them with a committed-baseline comparison:

* ``benchmarks/baselines/BENCH_<module>.json`` holds the accepted rows
  (seeded/refreshed with ``--update`` from a trusted run).
* A fresh run's rows are compared per name.  Two regimes, chosen by the
  row's unit:

  - **timing rows** (ms, s, tok/s, MB/s, blocks/s, streams/s, ms/tok,
    x): wall-clock on shared CI hosts is noisy, so these fail only
    past a wide regression band (default 3x worse than baseline).
    Improvements never fail — the tool prints a stale-baseline notice
    instead.
  - **structural rows** (bytes, ratios, counts, bools, error
    fractions): deterministic given the workload seeds, so these get a
    tight relative band (default 2%).

* **Floor rules** gate specific rows absolutely, independent of the
  baseline — the PR-acceptance thresholds that must hold on any host.

Rows present only in the baseline (vanished) or only in the fresh run
(unbaselined) fail too — a renamed metric must touch the baseline file
in the same PR.

Usage:
  PYTHONPATH=src python -m tools.bench_diff --fresh bench-artifacts
  PYTHONPATH=src python -m tools.bench_diff --fresh bench-artifacts --update
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Tuple

DEFAULT_BASELINE_DIR = os.path.join("benchmarks", "baselines")

# Units whose rows are host-wall-clock (or derived from it): wide band.
TIMING_UNITS = {"ms", "s", "tok/s", "MB/s", "blocks/s", "streams/s",
                "ms/tok", "x", "GB/s"}

# Absolute floors (row name → minimum value): PR acceptance thresholds
# that hold regardless of the committed baseline.
FLOORS: Dict[str, float] = {
    # fleet scaling gate: 4 sharded devices must deliver >= 1.5x the
    # aggregate tok/s of one (modeled, deterministic — not host noise)
    "shard4_tok_s_gain": 1.5,
    # PNM read mode: at 512k context the device-side top-k gather must
    # hold >= 3x the link-bound full-readback throughput (modeled from
    # measured per-page tier costs — deterministic)
    "pnm_tok_s_gain_512k": 3.0,
    # a gather whose k covers every candidate must ship exactly the
    # classic readback bytes — an invariant, not a perf number
    "pnm_topk_byte_identical": 1.0,
}

# Rows that exist to be tracked, never gated (their value is the
# trajectory across PRs, not a pass/fail band) — matched by suffix.
TRACK_ONLY_SUFFIXES = ("_wall_ms",)


def _rows(path: str) -> Dict[str, Tuple[float, str]]:
    with open(path) as f:
        payload = json.load(f)
    out = {}
    for row in payload.get("rows", []):
        val = row.get("value")
        if isinstance(val, bool):
            val = float(val)
        if isinstance(val, (int, float)):
            out[row["name"]] = (float(val), row.get("unit", ""))
    return out


def _check_module(name: str, fresh: Dict[str, Tuple[float, str]],
                  base: Dict[str, Tuple[float, str]],
                  timing_factor: float, tight_rel: float
                  ) -> Tuple[List[str], List[str]]:
    """Returns (failures, notices) for one module's row set."""
    fails: List[str] = []
    notes: List[str] = []
    for row, floor in FLOORS.items():
        if row in fresh and fresh[row][0] < floor:
            fails.append(
                f"{name}: {row} = {fresh[row][0]:.4g} below the absolute "
                f"floor {floor:g}")
    for row in sorted(set(base) - set(fresh)):
        fails.append(f"{name}: baseline row {row} missing from fresh run")
    for row in sorted(set(fresh) - set(base)):
        fails.append(f"{name}: fresh row {row} has no baseline "
                     f"(seed it with --update)")
    for row in sorted(set(fresh) & set(base)):
        fv, unit = fresh[row]
        bv, _ = base[row]
        if row.endswith(TRACK_ONLY_SUFFIXES):
            continue
        if unit in TIMING_UNITS:
            # direction: bigger is better for rates/speedups, smaller
            # for times — infer from the unit
            worse = (fv > bv * timing_factor
                     if unit in ("ms", "s", "ms/tok")
                     else fv * timing_factor < bv)
            better = (fv * timing_factor < bv
                      if unit in ("ms", "s", "ms/tok")
                      else fv > bv * timing_factor)
            if worse:
                fails.append(
                    f"{name}: {row} = {fv:.4g} {unit} regressed past "
                    f"{timing_factor:g}x of baseline {bv:.4g}")
            elif better:
                notes.append(
                    f"{name}: {row} = {fv:.4g} {unit} beats baseline "
                    f"{bv:.4g} by >{timing_factor:g}x — refresh with "
                    f"--update")
        else:
            denom = max(abs(bv), 1e-12)
            if abs(fv - bv) / denom > tight_rel:
                fails.append(
                    f"{name}: {row} = {fv:.6g} vs baseline {bv:.6g} "
                    f"(structural row, band ±{tight_rel:.0%})")
    return fails, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="gate fresh BENCH_*.json artifacts against committed "
                    "baselines (see module docstring)")
    ap.add_argument("--fresh", required=True,
                    help="directory holding the fresh BENCH_*.json files "
                         "(a benchmark run's BENCH_JSON_DIR)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE_DIR,
                    help=f"baseline directory (default {DEFAULT_BASELINE_DIR})")
    ap.add_argument("--update", action="store_true",
                    help="write/refresh baselines from the fresh run "
                         "instead of gating (floors still checked)")
    ap.add_argument("--timing-factor", type=float, default=3.0,
                    help="allowed wall-clock regression factor for "
                         "timing-unit rows (default 3.0)")
    ap.add_argument("--tight-rel", type=float, default=0.02,
                    help="relative band for deterministic structural "
                         "rows (default 0.02)")
    args = ap.parse_args(argv)

    fresh_paths = sorted(glob.glob(os.path.join(args.fresh, "BENCH_*.json")))
    if not fresh_paths:
        print(f"[bench_diff] no BENCH_*.json under {args.fresh}")
        return 1
    failures: List[str] = []
    notices: List[str] = []
    for path in fresh_paths:
        fname = os.path.basename(path)
        module = fname[len("BENCH_"):-len(".json")]
        fresh = _rows(path)
        bpath = os.path.join(args.baseline, fname)
        if args.update:
            # floors still apply: a bad run must not become the baseline
            fails, _ = _check_module(module, fresh, fresh,
                                     args.timing_factor, args.tight_rel)
            if fails:
                failures.extend(fails)
                continue
            os.makedirs(args.baseline, exist_ok=True)
            with open(path) as src, open(bpath, "w") as dst:
                dst.write(src.read())
            print(f"[bench_diff] baseline updated: {bpath}")
            continue
        if not os.path.exists(bpath):
            failures.append(
                f"{module}: no baseline {bpath} (seed with --update)")
            continue
        fails, notes = _check_module(module, fresh, _rows(bpath),
                                     args.timing_factor, args.tight_rel)
        failures.extend(fails)
        notices.extend(notes)
    for n in notices:
        print(f"[bench_diff] note: {n}")
    if failures:
        for f in failures:
            print(f"[bench_diff] FAIL: {f}", file=sys.stderr)
        print(f"[bench_diff] {len(failures)} failure(s)", file=sys.stderr)
        return 1
    print(f"[bench_diff] OK: {len(fresh_paths)} module(s) within bands")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
