"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration is
its own JSON file (the ``file`` of its ``configs`` entry); the mix is
``traffic/<traffic>.json`` beside ``configs/``; each per-layer metric is
``metrics/<name>.py`` with a ``read(ctx)`` function; the reference and
the operation counts are ``servebench/families/<family>.py``.  Adding a
configuration, a mix or a metric is adding a file and an entry: nothing
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/serve
CHECKOUT = BENCH_DIR.parents[1]
BENCH_REL = BENCH_DIR.relative_to(CHECKOUT)


@dataclasses.dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict
    conf: dict           # the configuration file, as loaded
    mix: dict            # the traffic file, as loaded
    family: object       # the family module

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = self.root / BENCH_REL / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"servebench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load(workload: str, root: Path = CHECKOUT) -> Cell:
    """The cell ``workload`` of the ``BENCHMARK.json`` under ``root``."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / BENCH_REL / "traffic" / f"{w['traffic']}.json").read_text())
    family = importlib.import_module(f"servebench.families.{conf['family']}")
    return Cell(root, bench, w, conf, mix, family)
