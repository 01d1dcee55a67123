"""The reader of the batched decode step's rows: its number on a made-up
record and on a served run's counters, and ``None`` where the program
ran no batched step or has no such counters."""

import numpy as np
import pytest
from servebench_fixtures import REPO  # noqa: F401  (puts servebench on the path)
from servebench import program, spec

from repro import obs

CELLS = ("qwen2-0.5b.spill", "mistral-7b-v0.3.chat")
SPANS = {"model.sync": {"count": 40, "total_s": 0.4, "self_s": 0.4}}


def _read(cell=CELLS[0]):
    return spec.load(cell).reader("decode_batch_rows")(None)


@pytest.mark.parametrize("cell", CELLS)
def test_declared_for_both_cells(cell):
    got = {m["name"]: m for m in spec.load(cell).per_layer()}
    m = got["decode_batch_rows"]
    assert (m["unit"], m["better"], m["moves"]) == ("rows", "higher", "tok_s")
    assert m["layer"] == "model step (jitted decode_step)"
    assert m["workloads"] == list(CELLS)


@pytest.mark.parametrize("batches,rows,want", [(10, 75, 7.5), (4, 32, 8.0)])
def test_rows_per_step_on_a_record(monkeypatch, batches, rows, want):
    rec = {"spans": SPANS, "counters": {"model.decode_batches": batches,
                                        "model.decode_rows": rows}}
    monkeypatch.setattr(program, "record", lambda: rec)
    assert _read() == pytest.approx(want)


@pytest.mark.parametrize("counters", [{}, {"model.decode_batches": 0},
                                      {"sched.tokens": 9}],
                         ids=["none", "no-steps", "parent-counters"])
def test_no_batched_step_reads_none(monkeypatch, counters):
    """What the parent program (one step per request, no such counters)
    or a slice without a decode step gives."""
    monkeypatch.setattr(program, "record",
                        lambda: {"spans": SPANS, "counters": counters})
    assert _read() is None


def test_an_empty_record_reads_none():
    obs.reset()
    assert _read() is None


@pytest.mark.slow
def test_a_served_run_reads_its_rows(smoke_model):
    """Three requests from tick 0 with outputs 3, 5 and 5 tokens: four
    batched steps decode 2 + 4 + 4 rows."""
    from repro.runtime.paging import LOSSLESS_POLICY
    from repro.runtime.serving import ServeRequest, ServeScheduler

    cfg, params = smoke_model("qwen2-0.5b")
    rng = np.random.default_rng(1)
    sched = ServeScheduler(cfg, params, max_batch=3, device_kind="trace",
                           policy=LOSSLESS_POLICY, page_tokens=16,
                           hbm_kv_budget=1 << 12)
    reqs = [ServeRequest(req_id=i, arrival=0.0,
                         prompt=rng.integers(0, cfg.vocab, (1, 16)).astype(
                             np.int32), max_new_tokens=n)
            for i, n in enumerate((3, 5, 5))]
    obs.reset()
    obs.enable()
    try:
        sched.run(reqs)
        got = _read()
    finally:
        obs.disable()
        obs.reset()
    assert got == pytest.approx(10 / 4)
