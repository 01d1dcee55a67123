"""Flow rules: async discipline (R4), broad excepts (R5) and jit/kernel
purity (R6)."""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from .core import Diagnostic, FileContext, Rule

# ---------------------------------------------------------------------------
# R4 — submit_async must reach a wait on all paths
# ---------------------------------------------------------------------------

# Calls that discharge in-flight tickets: direct waits, whole-queue
# drains, and the pool/engine wrappers over them.
WAIT_SINKS = frozenset({
    "wait", "drain", "drain_reads", "quiesce", "flush_io",
    "settle_prefetched",
})

Pending = Dict[ast.Call, FrozenSet[str]]
Exit = Tuple[str, Pending]          # ("fall"|"return"|"break"|"continue", _)


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _target_names(target: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(target)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)}


def _has_wait(region: ast.AST) -> bool:
    return any(isinstance(c, ast.Call) and _call_name(c) in WAIT_SINKS
               for c in ast.walk(region))


def _submits_in(region: ast.AST) -> List[ast.Call]:
    return [n for n in ast.walk(region)
            if isinstance(n, ast.Call) and _call_name(n) == "submit_async"]


class _FuncAnalysis:
    """Path walk of one function for R4.

    ``pending`` maps each live ``submit_async`` call node to the names
    its tickets are bound to.  A statement discharges pending tickets
    when it waits (any :data:`WAIT_SINKS` call) or when they *escape* to
    code that can wait them — returned/yielded, stored into an attribute
    or subscript, or passed as a call argument.  ``raise`` paths are
    teardown, not violations.  Loops are walked as zero-or-one
    iterations (tickets born in a loop header are clean on the
    zero-iteration path: an empty iterable issued no tickets) and
    ``try`` handlers start from the pending set at try entry — a simple,
    documented over-approximation.
    """

    def __init__(self, fn: ast.AST) -> None:
        self.fn = fn

    # -- per-region transfer -------------------------------------------------
    def _discharge(self, region: ast.AST, shape: Optional[ast.stmt],
                   pending: Pending) -> Pending:
        out = dict(pending)
        if _has_wait(region):
            return {}
        if not out:
            return out
        bound_names = set().union(*out.values())
        mentioned = _names_in(region) & bound_names
        if not mentioned:
            return out
        escapes = False
        if isinstance(shape, ast.Return) and shape.value is not None:
            escapes = True
        elif isinstance(shape, ast.Expr) and isinstance(
                shape.value, (ast.Yield, ast.YieldFrom)):
            escapes = True
        elif isinstance(shape, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (shape.targets if isinstance(shape, ast.Assign)
                       else [shape.target])
            if any(isinstance(leaf, (ast.Attribute, ast.Subscript))
                   for t in targets for leaf in ast.walk(t)):
                escapes = True
        if not escapes:
            # passed onward as a call argument (self._account(tickets),
            # lst.append(t)) — the receiver owns the wait now
            for call in (c for c in ast.walk(region)
                         if isinstance(c, ast.Call)):
                arg_names: Set[str] = set()
                for a in call.args:
                    arg_names |= _names_in(a)
                for kw in call.keywords:
                    arg_names |= _names_in(kw.value)
                if arg_names & mentioned:
                    escapes = True
                    break
        if escapes:
            for call in [c for c, b in out.items() if b & mentioned]:
                out.pop(call)
        return out

    def _births(self, region: ast.AST,
                shape: Optional[ast.stmt]) -> Pending:
        """submit_async calls born (and not instantly discharged) here."""
        born: Pending = {}
        calls = _submits_in(region)
        if not calls or _has_wait(region):
            return born
        if isinstance(shape, ast.Return):
            return born                     # tickets returned to the caller
        if isinstance(shape, ast.Expr) and isinstance(
                shape.value, (ast.Yield, ast.YieldFrom)):
            return born
        nested_args: Set[ast.Call] = set()
        for c in ast.walk(region):
            if isinstance(c, ast.Call):
                for a in list(c.args) + [k.value for k in c.keywords]:
                    nested_args.update(
                        n for n in ast.walk(a)
                        if isinstance(n, ast.Call)
                        and _call_name(n) == "submit_async")
        names: FrozenSet[str] = frozenset()
        if isinstance(shape, (ast.Assign, ast.AnnAssign)):
            targets = (shape.targets if isinstance(shape, ast.Assign)
                       else [shape.target])
            if any(isinstance(t, (ast.Attribute, ast.Subscript, ast.Starred))
                   for t in targets):
                return born                 # stored outward: escapes
            got: Set[str] = set()
            for t in targets:
                got |= _target_names(t)
            names = frozenset(got)
        for call in calls:
            if call not in nested_args:
                born[call] = names
        return born

    def _transfer(self, region: ast.AST, shape: Optional[ast.stmt],
                  pending: Pending) -> Pending:
        out = self._discharge(region, shape, pending)
        out.update(self._births(region, shape))
        return out

    # -- block walk ----------------------------------------------------------
    def walk_block(self, stmts: List[ast.stmt],
                   pending: Pending) -> List[Exit]:
        paths: List[Pending] = [pending]
        exits: List[Exit] = []
        for stmt in stmts:
            nxt: List[Pending] = []
            for p in paths:
                for kind, out in self._walk_stmt(stmt, dict(p)):
                    if kind == "fall":
                        nxt.append(out)
                    else:
                        exits.append((kind, out))
            paths = nxt
            if not paths:
                break
        exits.extend(("fall", p) for p in paths)
        return self._dedup(exits)

    @staticmethod
    def _dedup(exits: List[Exit]) -> List[Exit]:
        seen = set()
        out = []
        for kind, p in exits:
            key = (kind, frozenset(p.keys()))
            if key not in seen:
                seen.add(key)
                out.append((kind, p))
        return out

    def _walk_stmt(self, stmt: ast.stmt, pending: Pending) -> List[Exit]:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return [("fall", pending)]      # nested defs analyzed separately
        if isinstance(stmt, ast.If):
            head = self._transfer(stmt.test, None, pending)
            out = self.walk_block(stmt.body, dict(head))
            out += self.walk_block(stmt.orelse, dict(head))
            return self._dedup(out)
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            return self._walk_loop(stmt, pending)
        if isinstance(stmt, ast.Try):
            return self._walk_try(stmt, pending)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            head = dict(pending)
            for item in stmt.items:
                head = self._transfer(item.context_expr, None, head)
            return self.walk_block(stmt.body, head)
        if isinstance(stmt, ast.Raise):
            return []                       # teardown path, not a violation
        if isinstance(stmt, ast.Break):
            return [("break", pending)]
        if isinstance(stmt, ast.Continue):
            return [("continue", pending)]
        out = self._transfer(stmt, stmt, pending)
        if isinstance(stmt, ast.Return):
            return [("return", out)]
        return [("fall", out)]

    def _walk_loop(self, stmt, pending: Pending) -> List[Exit]:
        header = (stmt.iter if isinstance(stmt, (ast.For, ast.AsyncFor))
                  else stmt.test)
        head = self._discharge(header, None, pending)
        body_entry = dict(head)
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            # tickets from a header submit bind to the loop target; the
            # zero-iteration path had no tickets, so `head` stays clean
            targets = frozenset(_target_names(stmt.target))
            for call in _submits_in(stmt.iter):
                body_entry[call] = targets
        else:
            body_entry.update(self._births(header, None))
        exits: List[Exit] = []
        for kind, p in self.walk_block(stmt.body, body_entry):
            exits.append(("fall" if kind in ("continue", "break") else kind,
                          p))
        exits += self.walk_block(stmt.orelse, dict(head))
        exits.append(("fall", head))        # zero-iteration path
        return self._dedup(exits)

    def _walk_try(self, stmt: ast.Try, pending: Pending) -> List[Exit]:
        exits: List[Exit] = []
        for kind, p in self.walk_block(stmt.body, dict(pending)):
            if kind == "fall" and stmt.orelse:
                exits.extend(self.walk_block(stmt.orelse, p))
            else:
                exits.append((kind, p))
        for handler in stmt.handlers:
            exits.extend(self.walk_block(handler.body, dict(pending)))
        if stmt.finalbody:
            merged: List[Exit] = []
            for kind, p in exits:
                for fkind, fp in self.walk_block(stmt.finalbody, p):
                    merged.append((kind if fkind == "fall" else fkind, fp))
            exits = merged
        return self._dedup(exits)

    def run(self) -> Set[ast.Call]:
        violations: Set[ast.Call] = set()
        for kind, p in self.walk_block(list(getattr(self.fn, "body", [])),
                                       {}):
            if kind in ("fall", "return"):
                violations.update(p.keys())
        return violations


class R4AsyncDiscipline(Rule):
    id = "R4"
    name = "async-discipline"
    doc = ("every function calling submit_async must reach a wait()/"
           "drain()/quiesce() — or hand the tickets to a caller that "
           "can — on all paths")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(_call_name(n) == "submit_async"
                       for stmt in fn.body for n in ast.walk(stmt)
                       if isinstance(n, ast.Call)):
                continue
            for call in sorted(_FuncAnalysis(fn).run(),
                               key=lambda c: (c.lineno, c.col_offset)):
                yield self.diag(
                    ctx, call,
                    f"`submit_async` tickets in `{fn.name}` may never be "
                    f"waited on some path — reach wait()/drain()/quiesce() "
                    f"or hand them to the caller",
                )


# ---------------------------------------------------------------------------
# R5 — broad excepts need a reasoned pragma
# ---------------------------------------------------------------------------

BROAD_NAMES = frozenset({"Exception", "BaseException"})


def _handler_is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    work = list(t.elts) if isinstance(t, ast.Tuple) else [t]
    for n in work:
        if isinstance(n, ast.Name) and n.id in BROAD_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in BROAD_NAMES:
            return True
    return False


def _reraises(handler: ast.ExceptHandler) -> bool:
    """True when the handler unconditionally re-raises (its breadth is
    transparent to callers — cleanup-then-propagate)."""
    body = handler.body
    return bool(body) and isinstance(body[-1], ast.Raise) \
        and body[-1].exc is None


class R5BroadExcept(Rule):
    id = "R5"
    name = "broad-except"
    doc = ("no bare `except Exception:` without a "
           "`# tracecheck: allow-broad-except(<reason>)` pragma; handlers "
           "that end in a bare re-raise are exempt")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not _handler_is_broad(node) or _reraises(node):
                continue
            if ctx.broad_except_reason(node.lineno):
                continue
            caught = ("bare except" if node.type is None
                      else "except " + ast.unparse(node.type))
            yield self.diag(
                ctx, node,
                f"broad `{caught}` swallows unrelated failures — narrow it "
                f"or justify with `# tracecheck: allow-broad-except(reason)`",
            )


# ---------------------------------------------------------------------------
# R6 — no host-sync / Python RNG inside jit or pallas kernels
# ---------------------------------------------------------------------------

HOST_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
HOST_ARRAY_FNS = frozenset({"asarray", "array", "frombuffer",
                            "ascontiguousarray"})
NUMPY_ALIASES = frozenset({"np", "numpy"})
# repro.obs spans: inside a traced body they would time the trace, once
OBS_SPANS = frozenset({"span", "tick"})


def _is_jit_decorator(dec: ast.AST) -> bool:
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return True
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return True
    if isinstance(dec, ast.Call):
        # functools.partial(jax.jit, ...) / partial(jit, ...)
        f = dec.func
        partial = (isinstance(f, ast.Name) and f.id == "partial") or \
            (isinstance(f, ast.Attribute) and f.attr == "partial")
        if partial and dec.args:
            return _is_jit_decorator(dec.args[0])
        return _is_jit_decorator(f)
    return False


def _traced_functions(tree: ast.AST) -> Dict[str, Tuple[ast.AST, str]]:
    """name -> (FunctionDef, why) for functions that run under tracing:
    jit-decorated, jax.jit-wrapped at module level, or passed to
    pallas_call (directly or through functools.partial).  Cross-module
    jit wrapping (``jax.jit(imported_fn)``) is out of scope — the body
    is not in this file."""
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    out: Dict[str, Tuple[ast.AST, str]] = {}
    for name, fn in fns.items():
        if any(_is_jit_decorator(d) for d in fn.decorator_list):
            out[name] = (fn, "jax.jit")
    partial_of: Dict[str, str] = {}     # alias = functools.partial(fn, ...)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            f = call.func
            is_partial = (isinstance(f, ast.Name) and f.id == "partial") or \
                (isinstance(f, ast.Attribute) and f.attr == "partial")
            tgt = node.targets[0]
            if is_partial and call.args and isinstance(call.args[0], ast.Name) \
                    and isinstance(tgt, ast.Name):
                partial_of[tgt.id] = call.args[0].id
            if isinstance(f, ast.Attribute) and f.attr == "jit" and call.args \
                    and isinstance(call.args[0], ast.Name) \
                    and call.args[0].id in fns:
                out[call.args[0].id] = (fns[call.args[0].id], "jax.jit")
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "pallas_call" \
                and node.args:
            first = node.args[0]
            cand: Optional[str] = None
            if isinstance(first, ast.Name):
                cand = partial_of.get(first.id, first.id)
            elif isinstance(first, ast.Call):
                cf = first.func
                is_partial = (isinstance(cf, ast.Name) and cf.id == "partial") \
                    or (isinstance(cf, ast.Attribute) and cf.attr == "partial")
                if is_partial and first.args \
                        and isinstance(first.args[0], ast.Name):
                    cand = first.args[0].id
            if cand in fns:
                out[cand] = (fns[cand], "pallas_call")
    return out


def _dotted(node: ast.AST) -> List[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


class R6JitPurity(Rule):
    id = "R6"
    name = "jit-purity"
    doc = ("no host synchronization (np.asarray, .item(), device_get, "
           "block_until_ready), Python-side RNG or program span "
           "(obs.span) inside jax.jit / pallas_call bodies")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for name, (fn, why) in sorted(_traced_functions(ctx.tree).items()):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                bad = self._why_banned(node)
                if bad:
                    yield self.diag(
                        ctx, node,
                        f"{bad} inside {why} body `{name}` — traced code "
                        f"must stay device-pure",
                    )

    @staticmethod
    def _why_banned(call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr in HOST_SYNC_METHODS:
            return f"host-sync call `.{f.attr}()`"
        parts = _dotted(f)
        if len(parts) >= 2:
            head, rest = parts[0], parts[1:]
            if head in NUMPY_ALIASES and rest[0] == "random":
                return f"host RNG `{'.'.join(parts)}`"
            if head == "random":
                return f"host RNG `{'.'.join(parts)}`"
            if head in NUMPY_ALIASES and rest[-1] in HOST_ARRAY_FNS:
                return f"host materialization `{'.'.join(parts)}`"
            if head == "jax" and rest[-1] == "device_get":
                return "host-sync call `jax.device_get`"
            if head == "obs" and rest[-1] in OBS_SPANS:
                return f"program span `{'.'.join(parts)}`"
        return None
