"""replay-taint: nondeterminism may not flow into journaled decisions.

The PR 18 flight recorder's whole contract is that replaying the
decision journal byte-reproduces the incident: every journaled value
and every journal-consulted decision (eviction victim order) must
be a function of journal state, never of wall-clock time,
process-local identity, or iteration order. One `time.monotonic()`
laundered into a journal field silently breaks `replay_journal.py`
forever after.

This rule runs a may-taint dataflow over the function CFG:

  * **sources** — calls that read nondeterministic ambient state:
    `time.*` wall clocks, the stdlib `random` module (NOT
    `jax.random`, which is keyed and deterministic), `os.urandom`,
    `os.getpid`, `uuid.uuid1/uuid4`, `threading.get_ident`,
    `secrets.*`, bare `id()`/`hash()` (address- and seed-dependent),
    and iterating a `set` display/constructor (order taint);
  * **propagation** — assignment from a tainted expression taints the
    target; a subscript store of a tainted value taints the base
    (`entry["ts"] = time.time()` taints `entry`); an ATTRIBUTE store
    taints the field path, not the object (`req.pages_t =
    time.monotonic()` taints `req.pages_t` — journaling
    `req.trace.id` stays clean), and a constructor call
    (`_Request(submit_time=now)`) taints per keyword field the same
    way; nested function/lambda bodies are separate scopes;
  * **sinks** — the journal entry points: `build_journal_event(...)`
    arguments, `.append(...)`/`.stamp_header(...)` on a receiver whose
    name mentions `journal`, functions the scan pass discovered to
    forward parameters into those (the scheduler's
    `_journal_submit`/`_journal_fault` wrappers —
    found transitively and PER PARAMETER, the lockorder call-summary
    idiom: `_timeline_record(dur_s=...)` is clean because `dur_s`
    never reaches the journal entry it writes, while its `rows=` does
    and is checked), and `return`s from a function marked
    `# replay-decision` (eviction-order choosers).

Escapes: a `# replay-exempt: <why>` comment (non-empty reason
required) on the sink line or the line above exempts a DELIBERATELY
non-replayed field — e.g. the journal's own `ts_unix_s` metadata
stamp, which replay never reads. Exemptions are annotations, not
suppressions — they don't count against the ratchet, mirroring
`# fault-boundary:`. `# oryxlint: disable=replay-taint` remains the
counted escape for everything else.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from .cfg import Bind, build_cfg
from .core import Checker, Finding, ParsedModule, RepoContext, dotted_name
from .dataflow import ForwardAnalysis

_EXEMPT_RE = re.compile(r"#\s*replay-exempt:\s*(\S.*)")
_DECISION_RE = re.compile(r"#\s*replay-decision\b")

# Exact dotted call names that read nondeterministic ambient state.
TAINT_CALLS = {
    "time.time", "time.monotonic", "time.perf_counter",
    "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
    "time.clock_gettime", "time.process_time",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "datetime.datetime.utcnow", "datetime.date.today", "date.today",
    "os.urandom", "os.getpid", "os.getrandom",
    "uuid.uuid1", "uuid.uuid4",
    "threading.get_ident", "threading.get_native_id",
    "random.random", "random.randint", "random.uniform",
    "random.choice", "random.choices", "random.shuffle",
    "random.sample", "random.randrange", "random.getrandbits",
    "random.gauss", "random.normalvariate", "random.betavariate",
    "secrets.token_hex", "secrets.token_bytes", "secrets.token_urlsafe",
    "secrets.randbelow", "secrets.choice",
}
# Bare builtins whose value is process-local (CPython address / seeded
# string hashing).
TAINT_BUILTINS = {"id", "hash"}

_SOURCE_DESCR = {
    "time.": "wall-clock read",
    "datetime.": "wall-clock read",
    "date.": "wall-clock read",
    "random.": "stdlib random draw",
    "os.urandom": "os entropy read",
    "os.getrandom": "os entropy read",
    "os.getpid": "process-local id",
    "uuid.": "nondeterministic uuid",
    "threading.": "thread-identity read",
    "secrets.": "os entropy read",
}

# Journal entry points: free/attr function names whose ARGUMENTS are
# journaled, and methods on journal-named receivers.
SINK_FUNCS = {"build_journal_event"}
SINK_METHODS = {"append", "stamp_header", "extend"}


def _describe_source(name: str) -> str:
    for prefix, desc in _SOURCE_DESCR.items():
        if name.startswith(prefix):
            return desc
    if name in TAINT_BUILTINS:
        return f"process-local `{name}()`"
    return "nondeterministic read"


def _source_call(call: ast.Call) -> str | None:
    dn = dotted_name(call.func)
    if dn is None:
        return None
    if dn in TAINT_CALLS:
        return dn
    # `self._clock()`-style indirection is invisible; only direct
    # module reads are sources.
    if isinstance(call.func, ast.Name) and dn in TAINT_BUILTINS:
        return dn
    return None


def _is_set_expr(expr: ast.expr) -> bool:
    if isinstance(expr, ast.Set) or isinstance(expr, ast.SetComp):
        return True
    if isinstance(expr, ast.Call):
        dn = dotted_name(expr.func)
        return dn == "set" or dn == "frozenset"
    return False


class _TaintScan(ast.NodeVisitor):
    """Taint evidence inside one expression: direct source calls plus
    reads of already-tainted names or field paths. Skips nested
    function/lambda bodies (separate scopes)."""

    def __init__(self, tainted: dict[str, tuple]):
        # name-or-dotted-path -> (src_line, src_desc)
        self.tainted = tainted
        self.hits: list[tuple[int, str]] = []  # (src_line, desc)

    def visit_Call(self, node: ast.Call) -> None:
        src = _source_call(node)
        if src is not None:
            self.hits.append(
                (node.lineno, _describe_source(src))
            )
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        dn = dotted_name(node)
        if dn is not None and dn in self.tainted:
            self.hits.append(self.tainted[dn])
            return  # the field hit; don't re-hit through the base
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load) and node.id in self.tainted:
            self.hits.append(self.tainted[node.id])

    def visit_Lambda(self, node) -> None:
        return

    def visit_FunctionDef(self, node) -> None:
        return

    def visit_AsyncFunctionDef(self, node) -> None:
        return


def _receiver_mentions_journal(func: ast.expr) -> bool:
    if not isinstance(func, ast.Attribute):
        return False
    recv = dotted_name(func.value)
    return recv is not None and "journal" in recv.lower()


class _Taint(ForwardAnalysis):
    """Facts: ("taint", var, src_line, src_desc). May-analysis."""

    may = True

    def __init__(self, checker: "ReplayTaintChecker"):
        self.checker = checker

    def _tainted_map(self, state) -> dict[str, tuple]:
        out: dict[str, tuple] = {}
        for fact in state:
            if fact[0] == "taint" and fact[1] not in out:
                out[fact[1]] = (fact[2], fact[3])
        return out

    def _expr_taint(self, expr, state) -> list[tuple[int, str]]:
        scan = _TaintScan(self._tainted_map(state))
        scan.visit(expr)
        return scan.hits

    def _kill(self, state, var: str):
        return frozenset(
            f for f in state
            if not (f[0] == "taint" and f[1] == var)
        )

    def _base_name(self, target: ast.expr) -> str | None:
        while isinstance(target, (ast.Subscript, ast.Attribute,
                                  ast.Starred)):
            target = target.value
        if isinstance(target, ast.Name):
            return target.id
        return None

    def _assign(self, state, targets, value):
        hits = self._expr_taint(value, state) if value is not None \
            else []
        for target in targets:
            if isinstance(target, (ast.Tuple, ast.List)):
                state = self._assign(state, target.elts, value)
                continue
            if isinstance(target, ast.Attribute):
                # Field-granular: `req.pages_t = time.monotonic()`
                # taints `req.pages_t`, not every use of `req`.
                path = dotted_name(target)
                if path is None:
                    continue
                state = self._kill(state, path)
                if hits:
                    line, desc = hits[0]
                    state = state | {("taint", path, line, desc)}
                continue
            direct = isinstance(target, ast.Name)
            name = self._base_name(target)
            if name is None:
                continue
            if direct:
                state = self._kill_prefix(state, name)
                ctor = self._ctor_fields(value, state)
                if ctor is not None:
                    # Constructor call: taint per tainted keyword
                    # field (`_Request(submit_time=now)` taints
                    # `req.submit_time`), whole-object only for
                    # tainted positionals.
                    whole, fields = ctor
                    for field, (line, desc) in fields.items():
                        state = state | {
                            ("taint", f"{name}.{field}", line, desc)
                        }
                    if whole:
                        line, desc = whole
                        state = state | {("taint", name, line, desc)}
                    continue
            if hits:
                # A store through a subscript taints the base object
                # without clearing its other taints.
                line, desc = hits[0]
                state = state | {("taint", name, line, desc)}
        return state

    def _kill_prefix(self, state, name: str):
        """Re-binding a name clears the name AND its field facts."""
        prefix = name + "."
        return frozenset(
            f for f in state
            if not (
                f[0] == "taint"
                and (f[1] == name or f[1].startswith(prefix))
            )
        )

    def _ctor_fields(self, value, state):
        """(whole_taint | None, {field: (line, desc)}) when `value`
        is a constructor call (Capitalized final name — the repo's
        dataclass/class convention), else None."""
        if not isinstance(value, ast.Call):
            return None
        dn = dotted_name(value.func)
        if dn is None:
            return None
        last = dn.split(".")[-1].lstrip("_")
        if not last or not last[0].isupper():
            return None
        whole = None
        for arg in value.args:
            h = self._expr_taint(arg, state)
            if h:
                whole = h[0]
                break
        fields = {}
        for kw in value.keywords:
            h = self._expr_taint(kw.value, state)
            if h:
                if kw.arg is None:  # **kwargs splat: whole-object
                    whole = whole or h[0]
                else:
                    fields[kw.arg] = h[0]
        return whole, fields

    def transfer(self, elem, state):
        if isinstance(elem, Bind):
            if elem.kind == "for" and elem.target is not None \
                    and elem.value is not None:
                hits = self._expr_taint(elem.value, state)
                if _is_set_expr(elem.value):
                    hits = hits + [(
                        elem.value.lineno, "set iteration order"
                    )]
                name = self._base_name(elem.target)
                if name is not None:
                    state = self._kill(state, name)
                    if hits:
                        line, desc = hits[0]
                        state = state | {
                            ("taint", name, line, desc)
                        }
            return state
        if isinstance(elem, ast.Assign):
            return self._assign(state, elem.targets, elem.value)
        if isinstance(elem, ast.AnnAssign):
            return self._assign(state, [elem.target], elem.value)
        if isinstance(elem, ast.AugAssign):
            hits = self._expr_taint(elem.value, state)
            name = self._base_name(elem.target)
            if hits and name is not None:
                line, desc = hits[0]
                state = state | {("taint", name, line, desc)}
            return state
        return state


def _callee_last(call: ast.Call) -> str | None:
    func = call.func
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _direct_sink(call: ast.Call) -> str | None:
    last = _callee_last(call)
    if last in SINK_FUNCS:
        return "journal event build"
    if last in SINK_METHODS and _receiver_mentions_journal(call.func):
        return "journal write"
    return None


def _effective_params(params: tuple, call: ast.Call) -> tuple:
    if params and params[0] in ("self", "cls") \
            and isinstance(call.func, ast.Attribute):
        return params[1:]
    return params


class ReplayTaintChecker(Checker):
    name = "replay-taint"

    def __init__(self) -> None:
        # Scan-pass function summaries: simple name -> [param tuples]
        # (one per def; name collisions keep every signature and the
        # check stays conservative across them).
        self._sigs: dict[
            str, list[tuple[tuple, ast.AST, ParsedModule]]
        ] = {}
        # name -> frozenset of params that flow into a journal sink —
        # computed transitively (fixpoint) on first use.
        self._forwarded: dict[str, frozenset] | None = None

    # -- scan --------------------------------------------------------------

    def scan(self, mod: ParsedModule, ctx: RepoContext) -> None:
        for node in mod.nodes_of(
            ast.FunctionDef, ast.AsyncFunctionDef
        ):
            args = node.args
            params = tuple(
                a.arg for a in
                args.posonlyargs + args.args + args.kwonlyargs
            )
            self._sigs.setdefault(node.name, []).append(
                (params, node, mod)
            )

    def _registry(self) -> dict[str, frozenset]:
        """fn name -> params that reach a journal sink from inside it,
        found to a fixpoint: `_timeline_record` forwards `rows` (it
        lands in its `step` journal entry) but NOT `dur_s` (timeline
        only), so callers' wall-clock durations stay clean while
        anything feeding journaled fields is checked — per parameter,
        transitively through wrappers (the lockorder may-acquire
        idiom)."""
        if self._forwarded is not None:
            return self._forwarded
        forwarded: dict[str, frozenset] = {}
        # Call lists are re-read every fixpoint round — collect them
        # once per signature up front.
        cands = []
        for name, sigs in self._sigs.items():
            for params, node, smod in sigs:
                pset = set(params)
                if not pset:
                    continue
                calls = [
                    c for c in smod.walk(node)
                    if isinstance(c, ast.Call)
                ]
                cands.append((name, pset, calls))
        changed = True
        while changed:
            changed = False
            for name, pset, calls in cands:
                have = set(forwarded.get(name, frozenset()))
                for call in calls:
                    for value in self._sink_values(
                        call, forwarded
                    ):
                        for n in ast.walk(value):
                            if isinstance(n, ast.Name) \
                                    and n.id in pset:
                                have.add(n.id)
                if have != set(forwarded.get(name, frozenset())):
                    forwarded[name] = frozenset(have)
                    changed = True
        self._forwarded = forwarded
        return forwarded

    def _sink_values(
        self, call: ast.Call, forwarded: dict[str, frozenset]
    ) -> list[ast.expr]:
        """The argument expressions of `call` that reach a journal
        sink: every arg for direct sinks; only the args bound to
        forwarded parameters for discovered wrappers."""
        if _direct_sink(call) is not None:
            return list(call.args) + [
                kw.value for kw in call.keywords
            ]
        last = _callee_last(call)
        fparams = forwarded.get(last)
        if not fparams:
            return []
        out: list[ast.expr] = []
        for params, _node, _mod in self._sigs.get(last, ()):
            eff = _effective_params(params, call)
            for i, arg in enumerate(call.args):
                if i < len(eff) and eff[i] in fparams:
                    out.append(arg)
            for kw in call.keywords:
                if kw.arg is None or kw.arg in fparams:
                    out.append(kw.value)
        return out

    def _sink_what(self, call: ast.Call) -> str | None:
        direct = _direct_sink(call)
        if direct is not None:
            return direct
        last = _callee_last(call)
        if self._registry().get(last):
            return f"journal entry point `{last}`"
        return None

    # -- check -------------------------------------------------------------

    def _exempt(self, mod: ParsedModule, line: int) -> bool:
        for ln in (line, line - 1):
            m = _EXEMPT_RE.search(mod.comment_text(ln))
            if m and m.group(1).strip():
                return True
        return False

    def _is_decision_fn(self, mod: ParsedModule, fn) -> bool:
        first = min(
            [fn.lineno] + [d.lineno for d in fn.decorator_list]
        )
        if _DECISION_RE.search(mod.comment_text(fn.lineno)):
            return True
        line = first - 1
        while line >= 1:
            text = mod.comment_text(line)
            if not text:
                break
            if _DECISION_RE.search(text):
                return True
            line -= 1
        return False

    def check(
        self, mod: ParsedModule, ctx: RepoContext
    ) -> Iterator[Finding]:
        registry = self._registry()
        for node in mod.nodes_of(
            ast.FunctionDef, ast.AsyncFunctionDef
        ):
            if not (
                self._may_sink(mod, node, registry)
                or self._is_decision_fn(mod, node)
            ):
                continue
            yield from self._check_fn(mod, node)

    def _may_sink(self, mod, fn, registry) -> bool:
        """Cheap superset test: the taint pass can only report a
        function that contains a journal sink call (direct or via a
        discovered wrapper)."""
        for n in mod.walk(fn):
            if isinstance(n, ast.Call):
                if _direct_sink(n) is not None:
                    return True
                if registry.get(_callee_last(n)):
                    return True
        return False

    def _check_fn(self, mod, fn):
        flow = _Taint(self)
        cfg = build_cfg(fn.body, anchor=fn)
        flow.run(cfg)
        decision = self._is_decision_fn(mod, fn)
        reported: set = set()
        for block in cfg.blocks:
            for elem, state in flow.replay(block):
                node = elem.node if isinstance(elem, Bind) else elem
                root = elem.value if isinstance(elem, Bind) else elem
                if root is None:
                    continue
                yield from self._check_elem(
                    mod, fn, node, root, state, flow, decision,
                    reported,
                )

    def _check_elem(self, mod, fn, node, root, state, flow,
                    decision, reported):
        for call in mod.walk(root):
            if not isinstance(call, ast.Call):
                continue
            what = self._sink_what(call)
            if what is None:
                continue
            hits = []
            for v in self._sink_values(call, self._registry()):
                hits.extend(flow._expr_taint(v, state))
            if not hits:
                continue
            key = (call.lineno, call.col_offset)
            if key in reported:
                continue
            reported.add(key)
            if self._exempt(mod, call.lineno):
                continue
            line, desc = hits[0]
            yield self.finding(
                mod, call,
                f"nondeterministic value ({desc} at line {line}) "
                f"flows into {what}: journaled state must replay "
                "byte-identically — derive it from journal/ledger "
                "state, or mark a deliberately non-replayed field "
                "with `# replay-exempt: <why>`",
            )
        if decision and isinstance(root, ast.Return) \
                and root.value is not None:
            hits = flow._expr_taint(root.value, state)
            key = ("ret", root.lineno)
            if hits and key not in reported:
                reported.add(key)
                if not self._exempt(mod, root.lineno):
                    line, desc = hits[0]
                    yield self.finding(
                        mod, root,
                        f"`{fn.name}` is marked # replay-decision "
                        f"but returns a nondeterministic value "
                        f"({desc} at line {line}): replayed "
                        "decisions must be functions of journal "
                        "state only",
                    )
