"""Helpers of the serving benchmark's tests: paths, and a throwaway
benchmark root with a tiny cell."""

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = REPO / "benchmarks" / "serve"
for p in (BENCH, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = "tiny.tinyspill"


def make_root(root: Path) -> Path:
    """A benchmark root beside the repo's: its BENCHMARK.json plus one
    new configuration, one new mix and one new metric, added as files and
    entries only."""
    (root / "benchmarks" / "serve").mkdir(parents=True)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / d, root / "benchmarks" / "serve" / d)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    conf["name"] = "tiny"
    conf["config"].update(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=512)
    conf["check"] = {"logit_rmse": 0.02, "max_logit_gap": 0.05, "sample_requests": 2,
                     "min_tokens": 10}
    (root / "benchmarks/serve/configs/tiny.json").write_text(json.dumps(conf))
    mix = json.loads((BENCH / "traffic" / "spill.json").read_text())
    mix.update(clients=2, cycle=8, prompt_tokens={"values": [32]},
               output_tokens={"lognormal_median": 8, "sigma": 1.0,
                              "min": 4, "max": 24})
    mix["serve"]["hbm_kv_tokens"] = 16
    (root / "benchmarks/serve/traffic/tinyspill.json").write_text(json.dumps(mix))
    (root / "benchmarks/serve/metrics/ticks_per_s.py").write_text(
        "def read(ctx):\n"
        "    n = ctx.spans.count['sched.step']\n"
        "    return n / ctx.window_s if n else None\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/serve/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY, "config": "tiny",
                               "traffic": "tinyspill", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                               "better": "higher", "source": "program_span",
                               "layer": "scheduler", "moves": "tok_s",
                               "workloads": [TINY]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
