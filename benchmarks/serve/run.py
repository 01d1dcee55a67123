#!/usr/bin/env python3
"""Serve one benchmark cell on the chip and print its result line.

    python benchmarks/serve/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is a model
configuration under a traffic mix.  The run draws the weights and the
requests from ``--seed``, warms up every shape the mix uses, then drives
``ServeScheduler`` with a closed loop of clients for ``--seconds``.  With
``--trace 0`` the last line of standard output holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
host spans and a profiler trace of the window's last seconds.  Either way
a sample of the requests served in the window is checked against the
plain float32 reference, and the numbers compared are printed beside
their limits.

It needs a TPU: where JAX finds none, or fewer chips than the cell asks
for, it exits with code 3 and prints no result.  ``--control`` also
reads the float8 control on the same tokens; ``--extra-seeds`` repeats
the run in the same process for more seeds (calibration only).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(CHECKOUT / "src"))
# JAX's persistent compilation cache lives at a fixed path in the
# checkout; the program takes the directory from this variable.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--extra-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from servebench import spec

    cell = spec.load(args.workload, CHECKOUT)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # every program is kept, none evicted: eviction keeps an access-time
    # file beside each entry and cannot write into a directory that holds
    # entries without one
    jax.config.update("jax_compilation_cache_max_size", -1)
    from servebench import cell as runner

    t_start = T_START
    for seed in [args.seed, *args.extra_seeds]:
        out = runner.run(cell, seed, args.seconds, bool(args.trace), t_start,
                         control=args.control)
        print(json.dumps(out), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
