#!/usr/bin/env python
"""Docs-consistency gate: every `symbol` in the architecture docs must
resolve to something real in the ``repro`` package.

Scans docs/ARCHITECTURE.md and README.md for backtick-quoted tokens that
look like Python identifiers (bare ``submit`` or dotted
``TierStore.delete_prefix``) and verifies each one resolves:

* as a module path under ``repro`` (``repro.core.tier``);
* as a module-level attribute of any ``repro`` module (``ServeScheduler``);
* as an attribute / method / dataclass field of any class defined in
  ``repro`` (``submit``, ``queue_delay_s``);
* via attribute walk for dotted names (``LinkModel.schedule``);
* as a registered string name — layout (``bitplane-kv``), device kind
  (``trace``), codec (``lz4``), request kind (``kv``) or arrival kind
  (``poisson``) — so the docs can quote the vocabulary users actually
  pass in.

Tokens that are clearly not symbols are skipped: anything with spaces,
``/``, CLI ``--flags``, file names with known extensions, pure numbers,
and Python keywords/literals.  Unresolved tokens fail the run (exit 1)
with file:line positions — CI runs this after the test suite, so the
docs cannot silently drift from the code.

Run: PYTHONPATH=src python tools/check_docs.py
"""

from __future__ import annotations

import importlib
import inspect
import keyword
import pkgutil
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DOC_FILES = [ROOT / "docs" / "ARCHITECTURE.md", ROOT / "README.md"]

# Modules whose import has side effects unfit for a checker process
# (dryrun forces a 512-device XLA host platform).
SKIP_MODULES = {"repro.launch.dryrun"}

# A backtick token must fully match this to be treated as a symbol.
IDENT = re.compile(
    r"^[A-Za-z_][A-Za-z0-9_-]*(\.[A-Za-z_][A-Za-z0-9_-]*)*$"
)
FILE_EXT = re.compile(r"\.(md|py|yml|yaml|json|toml|txt|sh|cfg)$")
SKIP_WORDS = set(keyword.kwlist) | {"True", "False", "None",
                                    "isinstance", "setattr", "getattr"}


def iter_backtick_tokens(path: Path):
    """Yield (lineno, token) for every single-backtick span, skipping
    fenced code blocks (``` ... ```) — those are illustrative code/ascii
    art, not symbol references."""
    fenced = False
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        if fenced:
            continue
        for m in re.finditer(r"`([^`]+)`", line):
            yield lineno, m.group(1).strip()


def is_candidate(tok: str) -> bool:
    if not IDENT.match(tok):
        return False
    if FILE_EXT.search(tok):
        return False
    if tok in SKIP_WORDS:
        return False
    return True


def build_symbol_tables():
    """Import every repro module; return (modules, bare_names, objects,
    string_names)."""
    import repro

    modules = {"repro": repro}
    for info in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        if info.name in SKIP_MODULES:
            continue
        try:
            modules[info.name] = importlib.import_module(info.name)
        # tracecheck: allow-broad-except(imports of optional env-specific modules may fail arbitrarily; warn and keep checking)
        except Exception as e:  # pragma: no cover - env-specific deps
            print(f"[check_docs] warning: cannot import {info.name}: {e}")

    bare: dict[str, list] = {}

    def add(name: str, obj):
        bare.setdefault(name, []).append(obj)

    for mod_name, mod in modules.items():
        add(mod_name.rsplit(".", 1)[-1], mod)
        for name in dir(mod):
            if name.startswith("__"):
                continue
            obj = getattr(mod, name)
            add(name, obj)
            if isinstance(obj, type):
                for attr in dir(obj):
                    if not attr.startswith("__"):
                        add(attr, None)
                for field in getattr(obj, "__dataclass_fields__", {}):
                    add(field, None)
            # constructor / function parameters are part of the documented
            # surface (``page_tokens``, ``kv_window``)
            target = obj.__init__ if isinstance(obj, type) else obj
            if callable(target):
                try:
                    for p in inspect.signature(target).parameters:
                        add(p, None)
                except (TypeError, ValueError):
                    pass

    # Registered string vocabularies the docs may quote.
    strings: set[str] = set()
    from repro.core import codec as codecs
    from repro.core import tier

    strings.update(tier.LAYOUTS)
    strings.update(tier.DEVICE_KINDS)
    strings.update(codecs.CODECS)
    strings.update((tier.TENSOR, tier.KV))
    strings.update(("poisson", "bursty"))   # synth.request_trace kinds
    strings.update(("logical", "physical"))  # ServeScheduler capacity models
    strings.update(("none", "default"))      # --degrade-ladder specs
    from repro.core import sharding
    strings.update(sharding.PLACEMENTS)      # fleet placement policies
    strings.add("TRACE_SHARDS")              # sharded-fleet env default
    # tracecheck rule ids + the sanitizer's invariant names (structured
    # vocabulary of tools/tracecheck and TierStore(sanitize=True))
    strings.update(("R1", "R2", "R3", "R4", "R5", "R6", "R1-R6",
                    "tracecheck", "tools.tracecheck", "tools/tracecheck",
                    "TRACE_SANITIZE"))
    strings.update(("recency", "attention"))  # ServeEngine importance modes
    from repro import obs
    strings.update(obs.SPAN_NAMES)            # program span and counter names
    strings.update(obs.COUNTER_NAMES)
    # bench rows gated by absolute floors (tools/bench_diff.py FLOORS)
    strings.update(("shard4_tok_s_gain", "pnm_tok_s_gain_512k",
                    "pnm_topk_byte_identical"))
    strings.update(("ledger-stored-equality", "receipt-conservation",
                    "busy-clock-monotonic", "inflight-window-bound",
                    "retire-cleanup", "refcount-conservation"))
    # jax public API the docs reference when describing R6 (not part of
    # repro's surface, but real names all the same)
    strings.update(("pallas_call", "block_until_ready"))
    return modules, bare, strings


def resolve(tok: str, modules, bare, strings) -> bool:
    if tok in strings:
        return True
    if "-" in tok:          # non-string-name tokens never contain dashes
        return False
    if tok in modules or f"repro.{tok}" in modules:
        return True
    parts = tok.split(".")
    if parts[0] == "repro":
        # longest importable module prefix, then attribute walk
        for cut in range(len(parts), 0, -1):
            prefix = ".".join(parts[:cut])
            if prefix in modules:
                return walk(modules[prefix], parts[cut:])
        return False
    if parts[0] not in bare:
        return False
    if len(parts) == 1:
        return True
    return any(obj is not None and walk(obj, parts[1:])
               for obj in bare[parts[0]])


def walk(obj, attrs) -> bool:
    for a in attrs:
        fields = getattr(obj, "__dataclass_fields__", {})
        if a in fields:
            obj = None      # fields are leaves: nothing to walk further
            continue
        if obj is None or not hasattr(obj, a):
            return False
        obj = getattr(obj, a)
    return True


def main() -> int:
    modules, bare, strings = build_symbol_tables()
    failures = []
    checked = 0
    for path in DOC_FILES:
        if not path.exists():
            failures.append((path, 0, "<file missing>"))
            continue
        for lineno, tok in iter_backtick_tokens(path):
            if not is_candidate(tok):
                continue
            checked += 1
            if not resolve(tok, modules, bare, strings):
                failures.append((path, lineno, tok))
    if failures:
        print(f"[check_docs] {len(failures)} unresolved symbol(s) "
              f"(of {checked} checked):")
        for path, lineno, tok in failures:
            print(f"  {path.relative_to(ROOT)}:{lineno}: `{tok}`")
        return 1
    print(f"[check_docs] OK: {checked} symbols resolve against repro")
    return 0


if __name__ == "__main__":
    sys.exit(main())
