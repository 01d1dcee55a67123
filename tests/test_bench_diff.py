"""tools/bench_diff: the benchmark regression gate's own behavior.

The gate replaced inline CI thresholds, so it needs its own negative
tests: absolute floors fire regardless of baseline, timing rows get the
wide band with unit-inferred direction, structural rows the tight band,
row-set drift (vanished/unbaselined) fails, track-only rows never gate,
and ``--update`` seeds baselines but still refuses floor-violating runs.
"""

import json
import os

from tools.bench_diff import main


def _write(dirpath, module, rows):
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, f"BENCH_{module}.json")
    with open(path, "w") as f:
        json.dump({"rows": [
            {"table": module, "name": n, "value": v, "unit": u, "note": ""}
            for n, v, u in rows
        ]}, f)
    return path


def _dirs(tmp_path):
    return str(tmp_path / "fresh"), str(tmp_path / "base")


def _run(fresh, base, *extra):
    return main(["--fresh", fresh, "--baseline", base, *extra])


def test_identical_rows_pass(tmp_path):
    fresh, base = _dirs(tmp_path)
    rows = [("encode_ms", 12.0, "ms"), ("stored_bytes", 4096, "bytes")]
    _write(fresh, "m", rows)
    _write(base, "m", rows)
    assert _run(fresh, base) == 0


def test_floor_fires_even_when_baseline_agrees(tmp_path):
    """pnm_tok_s_gain_512k < 3.0 fails even if the committed baseline is
    just as bad — floors are PR acceptance, not drift detection."""
    fresh, base = _dirs(tmp_path)
    rows = [("pnm_tok_s_gain_512k", 1.5, "x")]
    _write(fresh, "m", rows)
    _write(base, "m", rows)
    assert _run(fresh, base) == 1


def test_timing_band_direction_follows_unit(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(base, "m", [("step_ms", 10.0, "ms"), ("rate", 100.0, "tok/s")])
    # 4x slower time fails; 4x lower rate fails
    _write(fresh, "m", [("step_ms", 40.0, "ms"), ("rate", 100.0, "tok/s")])
    assert _run(fresh, base) == 1
    _write(fresh, "m", [("step_ms", 10.0, "ms"), ("rate", 20.0, "tok/s")])
    assert _run(fresh, base) == 1
    # within the 3x band (even 2x worse) passes; improvement passes too
    _write(fresh, "m", [("step_ms", 20.0, "ms"), ("rate", 900.0, "tok/s")])
    assert _run(fresh, base) == 0


def test_structural_rows_get_tight_band(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(base, "m", [("stored_bytes", 1000, "bytes")])
    _write(fresh, "m", [("stored_bytes", 1050, "bytes")])   # 5% drift
    assert _run(fresh, base) == 1
    _write(fresh, "m", [("stored_bytes", 1010, "bytes")])   # within 2%
    assert _run(fresh, base) == 0


def test_row_set_drift_fails_both_ways(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(base, "m", [("a", 1.0, "ms"), ("b", 2.0, "ms")])
    _write(fresh, "m", [("a", 1.0, "ms"), ("c", 3.0, "ms")])
    assert _run(fresh, base) == 1   # b vanished AND c unbaselined


def test_track_only_suffix_never_gates(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(base, "m", [("prefill_wall_ms", 5.0, "ms")])
    _write(fresh, "m", [("prefill_wall_ms", 500.0, "ms")])
    assert _run(fresh, base) == 0


def test_update_seeds_baseline_but_enforces_floors(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(fresh, "m", [("encode_ms", 12.0, "ms"),
                        ("pnm_tok_s_gain_512k", 3.4, "x")])
    assert _run(fresh, base, "--update") == 0
    assert _run(fresh, base) == 0       # seeded baseline now gates cleanly
    # a floor-violating run must not become the new baseline
    _write(fresh, "bad", [("pnm_tok_s_gain_512k", 1.2, "x")])
    assert _run(fresh, base, "--update") == 1
    assert not os.path.exists(os.path.join(base, "BENCH_bad.json"))


def test_missing_baseline_file_fails(tmp_path):
    fresh, base = _dirs(tmp_path)
    _write(fresh, "m", [("a", 1.0, "ms")])
    assert _run(fresh, base) == 1
