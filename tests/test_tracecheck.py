"""tracecheck: every rule fires on a violating fixture (negative tests),
pragmas suppress, toggles work, and the repo's own tree is clean.

Fixtures are written to ``tmp_path`` with ``tmp_path`` as the repo root,
so relative-path logic (R1 ownership, R2/R3 sanctioned-file exemption)
is exercised without depending on the live tree's layout.
"""

from pathlib import Path

from tools.tracecheck import ALL_RULES, ProjectIndex, run_paths
from tools.tracecheck.rules_flow import (
    R4AsyncDiscipline, R5BroadExcept, R6JitPurity,
)
from tools.tracecheck.rules_privacy import (
    R1PrivateAccess, R2IsinstanceDispatch, R3AccountingMutation,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def _index():
    """A small hand-built cross-file index (no live-tree scan)."""
    idx = ProjectIndex()
    idx.private_attrs = {
        "_ledger": {"src/repro/core/tier.py"},
        "_max_seq": {"src/repro/runtime/serving.py"},
    }
    idx.accounting_fields = {"dram_bytes_stored", "dram_bytes_read",
                             "blocks", "stored_bytes"}
    return idx


def _lint(tmp_path, source, rules, name="mod.py", index=None):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return run_paths([str(f)], rules, index=index or _index(),
                     repo_root=tmp_path)


# ---------------------------------------------------------------------------
# R1 — private attribute access
# ---------------------------------------------------------------------------

def test_r1_fires_on_foreign_private_access(tmp_path):
    diags = _lint(tmp_path, "x = sched._max_seq\n", [R1PrivateAccess()])
    assert [d.rule for d in diags] == ["R1"]
    assert "_max_seq" in diags[0].message
    assert diags[0].line == 1


def test_r1_allows_self_and_own_module(tmp_path):
    src = (
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._ledger = {}\n"
        "    def peek(self, other):\n"
        "        return other._ledger\n"   # same class → own private attr
    )
    assert _lint(tmp_path, src, [R1PrivateAccess()]) == []


def test_r1_allows_defining_module(tmp_path):
    diags = _lint(tmp_path, "x = store._ledger\n", [R1PrivateAccess()],
                  name="src/repro/core/tier.py")
    assert diags == []


def test_r1_pragma_suppresses(tmp_path):
    src = "x = sched._max_seq  # tracecheck: disable=R1\n"
    assert _lint(tmp_path, src, [R1PrivateAccess()]) == []


# ---------------------------------------------------------------------------
# R2 — isinstance dispatch on tier subtypes
# ---------------------------------------------------------------------------

def test_r2_fires_outside_tier(tmp_path):
    src = (
        "def f(dev):\n"
        "    if isinstance(dev, TraceDevice):\n"
        "        return 1\n"
    )
    diags = _lint(tmp_path, src, [R2IsinstanceDispatch()])
    assert [d.rule for d in diags] == ["R2"]
    assert "TraceDevice" in diags[0].message


def test_r2_tuple_and_attribute_forms(tmp_path):
    src = "ok = isinstance(x, (tier.WordLayout, int))\n"
    diags = _lint(tmp_path, src, [R2IsinstanceDispatch()])
    assert len(diags) == 1 and "WordLayout" in diags[0].message


def test_r2_sanctioned_in_tier(tmp_path):
    src = "y = isinstance(x, BitplaneLayout)\n"
    assert _lint(tmp_path, src, [R2IsinstanceDispatch()],
                 name="src/repro/core/tier.py") == []


def test_r2_unrelated_isinstance_clean(tmp_path):
    assert _lint(tmp_path, "y = isinstance(x, dict)\n",
                 [R2IsinstanceDispatch()]) == []


# ---------------------------------------------------------------------------
# R3 — accounting-field mutation outside the sanctioned helpers
# ---------------------------------------------------------------------------

def test_r3_fires_on_direct_mutation(tmp_path):
    src = "dev.stats.dram_bytes_stored += 100\n"
    diags = _lint(tmp_path, src, [R3AccountingMutation()])
    assert [d.rule for d in diags] == ["R3"]
    assert "dram_bytes_stored" in diags[0].message


def test_r3_plain_assign_also_fires(tmp_path):
    src = "rec.blocks = 0\n"
    diags = _lint(tmp_path, src, [R3AccountingMutation()])
    assert [d.rule for d in diags] == ["R3"]


def test_r3_exempt_in_tier_and_reads_clean(tmp_path):
    assert _lint(tmp_path, "self.stats.blocks += n\n",
                 [R3AccountingMutation()],
                 name="src/repro/core/tier.py") == []
    assert _lint(tmp_path, "total = dev.stats.blocks\n",
                 [R3AccountingMutation()]) == []


# ---------------------------------------------------------------------------
# R4 — submit_async must reach a wait on all paths
# ---------------------------------------------------------------------------

def test_r4_fires_on_dropped_tickets(tmp_path):
    src = (
        "def leak(dev, reqs):\n"
        "    tickets = dev.submit_async(reqs)\n"
        "    return None\n"
    )
    diags = _lint(tmp_path, src, [R4AsyncDiscipline()])
    assert [d.rule for d in diags] == ["R4"]
    assert "leak" in diags[0].message


def test_r4_fires_on_one_unwaited_branch(tmp_path):
    src = (
        "def maybe(dev, reqs, flag):\n"
        "    tickets = dev.submit_async(reqs)\n"
        "    if flag:\n"
        "        return [t.wait() for t in tickets]\n"
        "    return None\n"                       # tickets dropped here
    )
    diags = _lint(tmp_path, src, [R4AsyncDiscipline()])
    assert [d.rule for d in diags] == ["R4"]


def test_r4_clean_when_waited(tmp_path):
    src = (
        "def ok(dev, reqs):\n"
        "    tickets = dev.submit_async(reqs)\n"
        "    return [t.wait() for t in tickets]\n"
    )
    assert _lint(tmp_path, src, [R4AsyncDiscipline()]) == []


def test_r4_clean_when_escaping(tmp_path):
    # returned, stored on self, or handed to another call: the receiver
    # owns the wait now (the paging-pool idioms)
    src = (
        "def hand_back(dev, reqs):\n"
        "    return dev.submit_async(reqs)\n"
        "def stash(self, dev, reqs):\n"
        "    self._prefetched['k'] = dev.submit_async(reqs)\n"
        "def pass_on(self, dev, reqs):\n"
        "    ts = dev.submit_async(reqs)\n"
        "    self._account(ts)\n"
    )
    assert _lint(tmp_path, src, [R4AsyncDiscipline()]) == []


def test_r4_clean_on_quiesce(tmp_path):
    src = (
        "def drain_all(dev, reqs):\n"
        "    dev.submit_async(reqs)\n"
        "    dev.quiesce()\n"
    )
    assert _lint(tmp_path, src, [R4AsyncDiscipline()]) == []


# ---------------------------------------------------------------------------
# R5 — broad excepts need a reasoned pragma
# ---------------------------------------------------------------------------

def test_r5_fires_on_broad_except(tmp_path):
    src = (
        "try:\n"
        "    risky()\n"
        "except Exception:\n"
        "    pass\n"
    )
    diags = _lint(tmp_path, src, [R5BroadExcept()])
    assert [d.rule for d in diags] == ["R5"]


def test_r5_fires_on_bare_except(tmp_path):
    src = "try:\n    risky()\nexcept:\n    pass\n"
    diags = _lint(tmp_path, src, [R5BroadExcept()])
    assert [d.rule for d in diags] == ["R5"]


def test_r5_pragma_with_reason_allows(tmp_path):
    src = (
        "try:\n"
        "    risky()\n"
        "# tracecheck: allow-broad-except(third-party raises anything)\n"
        "except Exception:\n"
        "    pass\n"
    )
    assert _lint(tmp_path, src, [R5BroadExcept()]) == []


def test_r5_empty_reason_still_fires(tmp_path):
    src = (
        "try:\n"
        "    risky()\n"
        "# tracecheck: allow-broad-except()\n"
        "except Exception:\n"
        "    pass\n"
    )
    assert len(_lint(tmp_path, src, [R5BroadExcept()])) == 1


def test_r5_reraise_exempt_and_narrow_clean(tmp_path):
    src = (
        "try:\n"
        "    risky()\n"
        "except Exception:\n"
        "    cleanup()\n"
        "    raise\n"
        "try:\n"
        "    risky()\n"
        "except ValueError:\n"
        "    pass\n"
    )
    assert _lint(tmp_path, src, [R5BroadExcept()]) == []


# ---------------------------------------------------------------------------
# R6 — host-sync / RNG inside traced bodies
# ---------------------------------------------------------------------------

def test_r6_fires_on_host_sync_in_jit(tmp_path):
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x.item()\n"
    )
    diags = _lint(tmp_path, src, [R6JitPurity()])
    assert [d.rule for d in diags] == ["R6"]
    assert ".item()" in diags[0].message


def test_r6_fires_on_np_random_in_pallas_kernel(tmp_path):
    src = (
        "import numpy as np\n"
        "from jax.experimental import pallas as pl\n"
        "def _kern(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] + np.random.rand()\n"
        "def launch(x):\n"
        "    return pl.pallas_call(_kern, out_shape=None)(x)\n"
    )
    diags = _lint(tmp_path, src, [R6JitPurity()])
    assert [d.rule for d in diags] == ["R6"]
    assert "np.random" in diags[0].message


def test_r6_fires_on_module_level_jit_wrap(tmp_path):
    src = (
        "import jax, numpy as np\n"
        "def step(x):\n"
        "    return np.asarray(x)\n"
        "fast_step = jax.jit(step)\n"
    )
    diags = _lint(tmp_path, src, [R6JitPurity()])
    assert [d.rule for d in diags] == ["R6"]


def test_r6_fires_on_program_span_in_jit(tmp_path):
    src = (
        "import jax\n"
        "from repro import obs\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    with obs.span('model.decode'):\n"
        "        return x + 1\n"
    )
    diags = _lint(tmp_path, src, [R6JitPurity()])
    assert [d.rule for d in diags] == ["R6"]
    assert "obs.span" in diags[0].message


def test_r6_untraced_function_clean(tmp_path):
    src = (
        "import numpy as np\n"
        "def host_side(x):\n"
        "    return np.asarray(x).item()\n"
    )
    assert _lint(tmp_path, src, [R6JitPurity()]) == []


# ---------------------------------------------------------------------------
# runner plumbing
# ---------------------------------------------------------------------------

def test_rules_are_individually_toggleable(tmp_path):
    src = (
        "x = sched._max_seq\n"
        "ok = isinstance(d, TierStore)\n"
    )
    both = _lint(tmp_path, src, [R1PrivateAccess(), R2IsinstanceDispatch()])
    assert sorted(d.rule for d in both) == ["R1", "R2"]
    only_r2 = _lint(tmp_path, src, [R2IsinstanceDispatch()])
    assert [d.rule for d in only_r2] == ["R2"]


def test_diagnostic_format_is_file_line_col_rule(tmp_path):
    diags = _lint(tmp_path, "x = sched._max_seq\n", [R1PrivateAccess()])
    text = diags[0].format()
    assert text.startswith("mod.py:1:") and " R1 " in text


def test_syntax_error_reported_not_crashed(tmp_path):
    diags = _lint(tmp_path, "def broken(:\n", [R1PrivateAccess()])
    assert [d.rule for d in diags] == ["E0"]


def test_cli_exit_codes(tmp_path, capsys):
    from tools.tracecheck.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept Exception:\n    pass\n")
    assert main([str(bad), "--select", "R5"]) == 1
    assert main([str(bad), "--select", "R5", "--disable", "R5"]) == 0
    out = capsys.readouterr().out
    assert "R5" in out and "[tracecheck] OK" in out


def test_repo_tree_is_clean():
    """The acceptance gate, in-process: the live tree lints clean."""
    diags = run_paths(
        [str(REPO_ROOT / p) for p in ("src", "benchmarks", "examples")],
        [cls() for cls in ALL_RULES],
    )
    assert diags == [], "\n".join(d.format() for d in diags)
