"""Ordering & failure-atomicity contracts — happens-before and
rollback-on-raise verified over the PR 3 call graph.

The bug class hand-review kept catching — PR 9's mark-before-write
stale serve, PR 7's leaked log handler, PR 15's ship-before-ack
invariant — is a happens-before or failure-atomicity violation on
shared state.  Two analyzers make those orderings checked contracts:

  order_contract
    order-violation      a declared happens-before contract
                         (`# order: <a> before <b>`, grammar shared
                         with tsdbsan in tools/lint/annotations.py) is
                         violated: some function that sequences both
                         events has a path reaching a `<b>` site
                         (`# order-event: <b>`) with `<a>` still
                         undischarged.

  failure_atomicity
    atomicity-torn-on-raise   a multi-write guarded-state transition
                         (>= 2 writes to `# guarded-by:` attrs inside
                         one `with self.<lock>:` region, or a declared
                         `# atomic:` group) interleaves a fallible
                         call between its first and last write with no
                         rollback on the raising path (try/except or
                         finally that restores the involved state).
    install-leak-on-raise    a `# global-install` site armed in
                         `__init__` before later fallible construction
                         work, with no rollback on the failing path —
                         generalizes the PR 7 hand-hardening of
                         `TSDServer.__init__` into a rule.

order_contract semantics (resource_leak-style statement walk):

  * An `# order-event:` tag attaches to the statement on its line (or
    the line below a standalone comment).  On a `with` statement the
    event fires at block EXIT (permit released when the context
    closes).
  * Event emission is transitive: a statement emits every event its
    (uniquely resolved) callees emit, to a fixpoint over the call
    graph.  Resolution is stricter than blocking's — only unambiguous
    targets (self-methods, typed attributes, unique names) create
    edges, so a 4-way devirtualization blob can neither invent nor
    launder an ordering.
  * A function is verified for contract (a, b) only when it actually
    SEQUENCES the two events: it has at least one statement emitting
    `a` without `b` and one emitting `b` without `a`.  A statement
    emitting both delegates the ordering to its callee (verified
    there) and discharges `a` — the single-entry-point routing shape.
  * The walk is optimistic: `if` joins union the branches' discharged
    sets, `try` bodies/handlers/finally share one evolving set, and
    the walk continues past `return` (a dead-code reorder still
    reports).

failure_atomicity semantics (segment-local statement scan):

  * Writes pair only within one nesting level — two writes in opposite
    if/else branches can never interleave on a real path, so each
    conditionally-entered block is checked as its own segment and
    exposes only its fallible CALLS upward (a raise inside a branch
    does escape into the enclosing flow).  `with` bodies and
    unprotected `try` bodies are transparent; a protected try (handler
    or finally restores the involved state) discharges interior raises
    and propagates only its surviving writes.  return/break/continue
    are barriers; `raise` is a fallible event then a barrier.
  * Fallibility is a whitelist complement: builtins over well-typed
    operands, plumbing constructors, dict.pop-with-default, metrics
    accessors (labels/inc/observe) and injected clocks are infallible;
    every other call could raise and tear the transition.
  * install-leak protection is judged at the CALL site: a fallible
    call inside a try whose handler rolls back and re-raises cannot
    leak the install, no matter where it was armed.

Seeded contracts (the repo's real load-bearing orderings):

    memstore-write  before memstore-mark       (storage/memstore.py)
    wal-append      before replica-ship        (core/tsdb.py)
    wal-append      before ingest-ack          (tsd/rpcs.py)
    replica-ship    before ingest-ack          (tsd/rpcs.py)
    catch-up-pull   before rejoin-ready        (tsd/replication.py)
    response-write  before permit-release      (tsd/rpcs.py)
    wal-close       before flightrec-shutdown  (core/tsdb.py shutdown)
    spill-close     before flightrec-shutdown  (core/tsdb.py shutdown)

Suppressions, SARIF, baseline and --changed-only all inherit from the
runner; fixture/test scopes override the analyzed directories through
`ctx.bucket("ordering")["paths"]`.  `static_order_table()` exports the
contract + event tables tsdbsan's runtime order recorder cross-checks
against (tools/sanitize/order.py), mirroring `static_request_paths`.
"""

from __future__ import annotations

import ast

from tools.lint.annotations import (ClassAnnotations, atomic_annotation,
                                    install_annotation, order_contracts,
                                    order_events,
                                    self_attr as _self_attr)
from tools.lint.astindex import class_annotations, get_ast_index
from tools.lint.callgraph import get_callgraph, module_name
from tools.lint.core import Analyzer, Finding, LintContext, SourceFile

RULE_ORDER = "order-violation"
RULE_TORN = "atomicity-torn-on-raise"
RULE_INSTALL_LEAK = "install-leak-on-raise"

ORDERING_DIRS = ("opentsdb_tpu/",)

# --------------------------------------------------------------------- #
# Shared tag helpers                                                    #
# --------------------------------------------------------------------- #


def _tags_for_stmt(lines: list[str], st: ast.stmt) -> list[str]:
    """`# order-event:` names attached to one statement: inline on its
    first line, or on a standalone comment line directly above."""
    line = st.lineno
    if line <= len(lines):
        tags = order_events(lines[line - 1])
        if tags:
            return tags
    if line >= 2:
        above = lines[line - 2].strip()
        if above.startswith("#"):
            return order_events(above)
    return []


def _install_for_stmt(lines: list[str], st: ast.stmt) -> bool:
    """True when the statement carries a `# global-install` annotation
    (inline or standalone comment above)."""
    line = st.lineno
    if line <= len(lines) and install_annotation(lines[line - 1]):
        return True
    if line >= 2:
        above = lines[line - 2].strip()
        if above.startswith("#") and install_annotation(above):
            return True
    return False


# --------------------------------------------------------------------- #
# order_contract                                                        #
# --------------------------------------------------------------------- #


class _OrderAnalysis:
    """Whole-program event-emission fixpoint + per-function walks."""

    def __init__(self, ctx: LintContext):
        bucket = ctx.bucket("ordering")
        self.graph = get_callgraph(ctx)
        self.dirs = tuple(bucket.get("paths", ORDERING_DIRS))
        self.contracts: list[tuple[str, str]] = []
        self.contract_sites: dict[tuple[str, str], tuple[str, int]] = {}
        self.events: set[str] = set()
        self.fns: dict[str, tuple] = {}        # qname -> (fi, src, cls)
        self.fn_emits: dict[str, frozenset] = {}
        self._callee_cache: dict[int, tuple[str, ...]] = {}
        self._classes: dict[tuple[str, str], ClassAnnotations] = {}

    def in_scope(self, path: str) -> bool:
        return path.startswith(self.dirs) or \
            any(d in path for d in self.dirs)

    # -- call resolution (unambiguous targets only) -----------------------

    def _unique_callees(self, call: ast.Call, fi, cls) -> tuple[str, ...]:
        cached = self._callee_cache.get(id(call))
        if cached is not None:
            return cached
        recv_types = None
        f = call.func
        if isinstance(f, ast.Attribute):
            attr = _self_attr(f.value)
            if attr is not None and cls is not None:
                t = cls.attr_types.get(attr)
                if t is not None:
                    recv_types = {t}
        qnames = {info.qname
                  for info, _ctor, _cls in self.graph.resolve(
                      call, fi, recv_types=recv_types)
                  if info is not None and ".<nested>." not in info.qname}
        # an ambiguous devirtualization must neither invent nor launder
        # an ordering — only a single unambiguous target creates an edge
        out = tuple(sorted(qnames)) if len(qnames) == 1 else ()
        self._callee_cache[id(call)] = out
        return out

    # -- emission queries -------------------------------------------------

    def expr_emits(self, expr, fi, cls) -> set[str]:
        out: set[str] = set()
        if expr is None:
            return out
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                for q in self._unique_callees(sub, fi, cls):
                    out |= self.fn_emits.get(q, frozenset())
        return out

    def stmt_emits(self, st: ast.stmt, fi, src: SourceFile,
                   cls) -> frozenset:
        ev = set(_tags_for_stmt(src.lines, st))
        if not isinstance(st, (ast.With, ast.AsyncWith)):
            ev |= self.expr_emits(st, fi, cls)
        return frozenset(ev)

    # -- the pass ---------------------------------------------------------

    def run(self, ctx: LintContext) -> None:
        in_scope = [s for s in ctx.files if self.in_scope(s.path)]
        seen: set[tuple[str, str]] = set()
        for src in in_scope:
            for lineno, line in enumerate(src.lines, start=1):
                for pair in order_contracts(line):
                    if pair not in seen:
                        seen.add(pair)
                        self.contracts.append(pair)
                        self.contract_sites[pair] = (src.path, lineno)
                for name in order_events(line):
                    self.events.add(name)
        self._classes = get_ast_index(ctx).classes
        # collect functions + direct tags + edges
        direct: dict[str, set[str]] = {}
        edges: dict[str, set[str]] = {}
        for src in in_scope:
            mod = self.graph.modules.get(module_name(src.path))
            if mod is None:
                continue
            fns = list(mod.functions.values())
            for methods in mod.classes.values():
                fns.extend(methods.values())
            for fi in fns:
                cls = self._classes.get((src.path, fi.klass)) \
                    if fi.klass else None
                self.fns[fi.qname] = (fi, src, cls)
                tags: set[str] = set()
                outs: set[str] = set()
                for node in ast.walk(fi.node):
                    if isinstance(node, ast.stmt) and node is not fi.node \
                            and not isinstance(node, (ast.FunctionDef,
                                                      ast.AsyncFunctionDef,
                                                      ast.ClassDef)):
                        tags.update(_tags_for_stmt(src.lines, node))
                    if isinstance(node, ast.Call):
                        outs.update(self._unique_callees(node, fi, cls))
                direct[fi.qname] = tags
                edges[fi.qname] = outs
        # emission fixpoint over the call graph (cycles converge: the
        # union only grows and the event alphabet is finite)
        emits = {q: set(t) for q, t in direct.items()}
        changed = True
        while changed:
            changed = False
            for q, outs in edges.items():
                cur = emits[q]
                before = len(cur)
                for callee in outs:
                    cur |= emits.get(callee, set())
                if len(cur) != before:
                    changed = True
        self.fn_emits = {q: frozenset(e) for q, e in emits.items()}

    # -- pairing + verification -------------------------------------------

    def _fn_units(self, fi, src, cls) -> list[frozenset]:
        """Flat statement-level emission sets (pairing pre-pass)."""
        units: list[frozenset] = []

        def visit(stmts):
            for st in stmts:
                if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(st.body)
                    continue
                if isinstance(st, ast.ClassDef):
                    continue
                if isinstance(st, (ast.With, ast.AsyncWith)):
                    entry: set[str] = set()
                    for item in st.items:
                        entry |= self.expr_emits(item.context_expr, fi, cls)
                    if entry:
                        units.append(frozenset(entry))
                    tags = frozenset(_tags_for_stmt(src.lines, st))
                    if tags:
                        units.append(tags)
                    visit(st.body)
                    continue
                if isinstance(st, ast.If):
                    e = self.expr_emits(st.test, fi, cls)
                    if e:
                        units.append(frozenset(e))
                    visit(st.body)
                    visit(st.orelse)
                    continue
                if isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
                    ctrl = getattr(st, "test", None)
                    if ctrl is None:
                        ctrl = getattr(st, "iter", None)
                    e = self.expr_emits(ctrl, fi, cls)
                    if e:
                        units.append(frozenset(e))
                    visit(st.body)
                    visit(st.orelse)
                    continue
                if isinstance(st, ast.Try):
                    visit(st.body)
                    for h in st.handlers:
                        visit(h.body)
                    visit(st.orelse)
                    visit(st.finalbody)
                    continue
                e = self.stmt_emits(st, fi, src, cls)
                if e:
                    units.append(e)

        visit(fi.node.body)
        return units

    def verify(self) -> list[Finding]:
        findings: list[Finding] = []
        if not self.contracts:
            return findings
        for qname in sorted(self.fns):
            fi, src, cls = self.fns[qname]
            emitted = self.fn_emits.get(qname, frozenset())
            candidates = [(a, b) for (a, b) in self.contracts
                          if a in emitted and b in emitted]
            if not candidates:
                continue
            units = self._fn_units(fi, src, cls)
            active = [(a, b) for (a, b) in candidates
                      if any(a in u and b not in u for u in units)
                      and any(b in u and a not in u for u in units)]
            if not active:
                continue
            walker = _OrderWalk(self, fi, src, cls, active)
            walker.run()
            for line, (a, b) in walker.violations:
                decl = self.contract_sites.get((a, b))
                related = ((decl[0], decl[1],
                            "contract '%s before %s' declared here"
                            % (a, b)),) if decl else ()
                findings.append(Finding(
                    fi.path, line, RULE_ORDER,
                    "event '%s' can be reached before '%s' in '%s' — "
                    "violates the declared contract '# order: %s before "
                    "%s'; reorder so '%s' is discharged on every path "
                    "that crosses '%s' (or move the '# order-event' "
                    "tags with the code if the invariant moved)"
                    % (b, a, fi.name, a, b, a, b), related=related))
        return findings


class _OrderWalk:
    """Resource_leak-style statement walk of one function: maintain the
    set of discharged events at each program point; a statement emitting
    contract side `b` with side `a` undischarged is a violation."""

    def __init__(self, an: _OrderAnalysis, fi, src: SourceFile, cls,
                 contracts: list[tuple[str, str]]):
        self.an = an
        self.fi = fi
        self.src = src
        self.cls = cls
        self.contracts = contracts
        self.violations: list[tuple[int, tuple[str, str]]] = []
        self._seen: set[tuple[int, tuple[str, str]]] = set()

    def run(self) -> None:
        self._walk(self.fi.node.body, set())

    def _check(self, emits: frozenset, line: int,
               discharged: set) -> None:
        for (a, b) in self.contracts:
            if b in emits and a not in emits and a not in discharged:
                key = (line, (a, b))
                if key not in self._seen:
                    self._seen.add(key)
                    self.violations.append(key)
        discharged |= emits

    def _walk(self, stmts, discharged: set) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a closure runs later on behalf of this function; walk
                # it with a copy so its discharges stay local
                self._walk(st.body, set(discharged))
                continue
            if isinstance(st, ast.ClassDef):
                continue
            if isinstance(st, (ast.With, ast.AsyncWith)):
                entry: set[str] = set()
                for item in st.items:
                    entry |= self.an.expr_emits(item.context_expr,
                                                self.fi, self.cls)
                self._check(frozenset(entry), st.lineno, discharged)
                self._walk(st.body, discharged)
                # the statement's own tag fires at block EXIT
                tags = frozenset(_tags_for_stmt(self.src.lines, st))
                self._check(tags, st.lineno, discharged)
                continue
            if isinstance(st, ast.If):
                self._check(frozenset(self.an.expr_emits(
                    st.test, self.fi, self.cls)), st.lineno, discharged)
                d1 = set(discharged)
                self._walk(st.body, d1)
                d2 = set(discharged)
                self._walk(st.orelse, d2)
                # optimistic join: either branch's discharge counts
                discharged |= d1 | d2
                continue
            if isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
                ctrl = getattr(st, "test", None)
                if ctrl is None:
                    ctrl = getattr(st, "iter", None)
                self._check(frozenset(self.an.expr_emits(
                    ctrl, self.fi, self.cls)), st.lineno, discharged)
                self._walk(st.body, discharged)
                self._walk(st.orelse, discharged)
                continue
            if isinstance(st, ast.Try):
                self._walk(st.body, discharged)
                for h in st.handlers:
                    self._walk(h.body, discharged)
                self._walk(st.orelse, discharged)
                self._walk(st.finalbody, discharged)
                continue
            emits = self.an.stmt_emits(st, self.fi, self.src, self.cls)
            self._check(emits, st.lineno, discharged)


# --------------------------------------------------------------------- #
# failure_atomicity                                                     #
# --------------------------------------------------------------------- #

# Calls that cannot raise under the repo's idioms: builtins over
# well-typed operands, the threading/collections constructors the tree
# uses for plumbing, and side-effect-free accessors.  Everything else
# is treated as fallible — the analyzer asks "could a raise here tear
# the transition", and the answer for an arbitrary call is yes.
_INFALLIBLE_FUNCS = frozenset({
    "len", "int", "float", "str", "bool", "bytes", "abs", "round", "min",
    "max", "sum", "sorted", "all", "any", "id", "repr", "hash",
    "isinstance", "issubclass", "hasattr", "getattr", "tuple", "list",
    "dict", "set", "frozenset", "enumerate", "zip", "range", "iter",
    "print", "format", "type", "callable", "vars", "object",
})
_INFALLIBLE_CTORS = frozenset({
    "Lock", "RLock", "Condition", "Event", "Semaphore",
    "BoundedSemaphore", "Barrier", "deque", "defaultdict", "OrderedDict",
    "Counter", "Random", "WeakSet", "WeakValueDictionary",
})
_INFALLIBLE_METHODS = frozenset({
    "get", "items", "keys", "values", "copy", "append", "appendleft",
    "extend", "add", "discard", "clear", "setdefault", "update",
    "monotonic", "perf_counter", "time", "locked", "strip", "lstrip",
    "rstrip", "split", "join", "startswith", "endswith", "lower",
    "upper", "replace", "encode", "decode", "release", "notify",
    "notify_all",
    # numpy reductions over well-typed arrays
    "all", "any",
    # metrics plumbing: prometheus-style registries never raise from
    # labels()/inc()/observe(), and treating instrumentation as a
    # fallibility boundary would demand try/except around every gauge
    "labels", "inc", "dec", "observe",
    # injected clock callables (the repo's convention for testable time)
    "_clock",
})


def _fallible_label(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        if f.id in _INFALLIBLE_FUNCS or f.id in _INFALLIBLE_CTORS:
            return None
        return f.id
    if isinstance(f, ast.Attribute):
        if f.attr in _INFALLIBLE_METHODS or f.attr in _INFALLIBLE_CTORS:
            return None
        if f.attr == "pop" and len(call.args) + len(call.keywords) >= 2:
            # dict.pop(key, default) cannot raise; one-arg pop can
            return None
        return f.attr
    return "call"


def _calls_in(expr):
    """Calls in one expression, excluding lambda/comprehension-deferred
    bodies is overkill for this tree — but lambdas genuinely defer, so
    their bodies are skipped."""
    if expr is None:
        return
    stack = [expr]
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Lambda):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _write_targets(st) -> list[str]:
    """self-attribute names written by one assignment statement
    (`self.a = ...`, `self.a[k] = ...`, `self.a += ...`, tuples)."""
    if isinstance(st, ast.Assign):
        targets = list(st.targets)
    elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
        targets = [st.target]
    else:
        return []
    out: list[str] = []
    queue = list(targets)
    while queue:
        t = queue.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            queue.extend(t.elts)
            continue
        if isinstance(t, ast.Subscript):
            t = t.value
        attr = _self_attr(t)
        if attr is not None:
            out.append(attr)
    return out


def _writes_any(stmts, attrs: set) -> bool:
    for st in stmts:
        for node in ast.walk(st):
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                if any(a in attrs for a in _write_targets(node)):
                    return True
    return False


def _has_call(stmts) -> bool:
    return any(isinstance(n, ast.Call)
               for st in stmts for n in ast.walk(st))


def _try_restores(tr: ast.Try, attrs: set) -> bool:
    """A try whose handler or finally visibly restores the involved
    state (writes one of the attrs, or runs a rollback call) protects
    the transition — optimistic, like every join in this suite."""
    for h in tr.handlers:
        if _writes_any(h.body, attrs) or _has_call(h.body):
            return True
    if tr.finalbody and (_writes_any(tr.finalbody, attrs)
                         or _has_call(tr.finalbody)):
        return True
    return False


_BARRIER = ("barrier", 0, None)


def _torn_findings(events: list[tuple], attrs_label: str, fn_name: str,
                   path: str) -> list[Finding]:
    write_idx = [i for i, e in enumerate(events) if e[0] == "write"]
    if len({events[i][2] for i in write_idx}) < 2:
        return []
    first, last = write_idx[0], write_idx[-1]
    for i in range(first + 1, last):
        if events[i][0] == "call":
            involved = sorted({events[j][2] for j in write_idx})
            return [Finding(
                path, events[i][1], RULE_TORN,
                "transition over %s ('%s', %s) interleaves fallible "
                "'%s' between its writes — a raise there leaves the "
                "state half-applied; finish the writes before the "
                "call, hoist it out of the transition, or roll back "
                "in try/except-finally"
                % (attrs_label, "', '".join(involved), fn_name,
                   events[i][2]))]
    return []


def _segment_findings(stmts, attrs: set, attrs_label: str, fn_name: str,
                      path: str) -> list[Finding]:
    """Torn-transition findings for one region, segment-locally.

    Writes pair only with writes at the SAME nesting level: two writes
    in different branches of an if/else can never interleave on a real
    path, so a conditionally-entered block is checked as its own
    segment and exposes only its fallible CALLS to the enclosing flow
    (a raise inside the branch does escape, so it still interleaves the
    parent's writes).  `with` bodies and unprotected `try` bodies
    execute in the enclosing flow and are transparent.  A protected try
    (handler/finally restores the involved state) discharges interior
    raises: its surviving writes propagate, its calls do not.  return/
    break/continue are barriers — events on the two sides of one cannot
    interleave; `raise` is a fallible event followed by a barrier.
    """
    findings: list[Finding] = []

    def emit(evs):
        chunk: list[tuple] = []
        for e in evs + [_BARRIER]:
            if e[0] == "barrier":
                findings.extend(_torn_findings(
                    chunk, attrs_label, fn_name, path))
                chunk = []
            else:
                chunk.append(e)

    def check(body, checked=True):
        """Check a conditionally-entered block as its own segment;
        expose only its fallible calls to the enclosing flow.
        ``checked=False`` (inside a protected try) collects without
        reporting — interior raises are rolled back by the handler."""
        evs = collect(body, checked)
        if checked:
            emit(evs)
        return [e for e in evs if e[0] == "call"]

    def collect(body, checked=True):
        evs: list[tuple] = []

        def calls_of(expr):
            for c in _calls_in(expr):
                label = _fallible_label(c)
                if label is not None:
                    evs.append(("call", c.lineno, label))

        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Try):
                if _try_restores(st, attrs):
                    # raises inside are rolled back, so interior
                    # interleavings are discharged; writes that survive
                    # (the body completed) still pair with the
                    # enclosing flow's writes
                    for part in (st.body, st.orelse, st.finalbody):
                        evs.extend(e for e in collect(part, False)
                                   if e[0] == "write")
                    continue
                evs.extend(collect(st.body, checked))
                for h in st.handlers:
                    evs.extend(check(h.body, checked))
                evs.extend(collect(st.orelse, checked))
                evs.extend(collect(st.finalbody, checked))
                continue
            if isinstance(st, ast.If):
                calls_of(st.test)
                evs.extend(check(st.body, checked))
                evs.extend(check(st.orelse, checked))
                continue
            if isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
                calls_of(getattr(st, "test", None) or
                         getattr(st, "iter", None))
                evs.extend(check(st.body, checked))
                evs.extend(check(st.orelse, checked))
                continue
            if isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    calls_of(item.context_expr)
                evs.extend(collect(st.body, checked))
                continue
            if isinstance(st, (ast.Return, ast.Break, ast.Continue)):
                calls_of(getattr(st, "value", None))
                evs.append(_BARRIER)
                continue
            if isinstance(st, ast.Raise):
                calls_of(st.exc)
                evs.append(("call", st.lineno, "raise"))
                evs.append(_BARRIER)
                continue
            if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                calls_of(getattr(st, "value", None))
                for attr in _write_targets(st):
                    if attr in attrs:
                        evs.append(("write", st.lineno, attr))
                continue
            calls_of(st)
        return evs

    emit(collect(stmts))
    return findings


def _method_lock_regions(m, cls: ClassAnnotations):
    """(lock attr, body stmts) for each `with self.<lock>:` region."""
    for node in ast.walk(m):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            attr = _self_attr(item.context_expr)
            if attr is not None and attr in cls.locks:
                yield attr, node.body
                break


def _check_atomicity(src: SourceFile, ctx: LintContext) -> list[Finding]:
    dirs = tuple(ctx.bucket("ordering").get("paths", ORDERING_DIRS))
    if not (src.path.startswith(dirs) or any(d in src.path for d in dirs)):
        return []
    findings: list[Finding] = []
    per_file = class_annotations(ctx, src)
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        cls = per_file[node.name]
        groups: dict[str, set] = {}
        for attr, line in cls.init_lines.items():
            g = atomic_annotation(src.lines[line - 1]) if \
                line <= len(src.lines) else None
            if g is None and line >= 2:
                above = src.lines[line - 2].strip()
                if above.startswith("#"):
                    g = atomic_annotation(above)
            if g is not None:
                groups.setdefault(g, set()).add(attr)
        for m in node.body:
            if not isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if m.name != "__init__":
                # lock regions: >= 2 guarded attrs written in one
                for lock, body in _method_lock_regions(m, cls):
                    attrs = {a for a, lk in cls.guarded.items()
                             if lk == lock}
                    if len(attrs) < 2:
                        continue
                    findings.extend(_segment_findings(
                        body, attrs,
                        "lock '%s' state" % lock, m.name, src.path))
                # declared atomic groups: whole-method transitions
                # (__init__ is construction, not a transition — a raise
                # there never leaks a half-written instance)
                for gname, attrs in groups.items():
                    if len(attrs) < 2:
                        continue
                    findings.extend(_segment_findings(
                        m.body, attrs,
                        "atomic group '%s'" % gname, m.name, src.path))
            else:
                findings.extend(_init_install_leaks(m, src, node.name))
    return findings


def _handler_rolls_back(tr: ast.Try) -> bool:
    """A handler that re-raises AND takes a rollback action (a call or
    an attribute reset), or a finally that runs cleanup calls, covers
    raises inside this try."""
    for h in tr.handlers:
        has_raise = any(isinstance(n, ast.Raise) for n in ast.walk(h))
        has_action = any(isinstance(n, (ast.Call, ast.Assign))
                         for n in ast.walk(h))
        if has_raise and has_action:
            return True
    return bool(tr.finalbody) and _has_call(tr.finalbody)


def _init_install_leaks(m, src: SourceFile, cls_name: str
                        ) -> list[Finding]:
    events: list[tuple] = []          # (kind, line, label, protect_ids)

    def calls(expr, stack):
        for c in _calls_in(expr):
            label = _fallible_label(c)
            if label is not None:
                events.append(("call", c.lineno, label, stack))

    def visit(body, stack):
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Try):
                sub = stack + ((id(st),) if _handler_rolls_back(st)
                               else ())
                visit(st.body, sub)
                for h in st.handlers:
                    visit(h.body, sub)
                visit(st.orelse, sub)
                visit(st.finalbody, stack)
                continue
            if isinstance(st, (ast.If, ast.While, ast.For, ast.AsyncFor)):
                calls(getattr(st, "test", None) or
                      getattr(st, "iter", None), stack)
                visit(st.body, stack)
                visit(st.orelse, stack)
                continue
            if isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    calls(item.context_expr, stack)
                visit(st.body, stack)
                continue
            # argument/value calls evaluate before the install arms
            calls(st, stack)
            if _install_for_stmt(src.lines, st):
                events.append(("install", st.lineno, None, stack))

    visit(m.body, ())
    findings: list[Finding] = []
    for i, ev in enumerate(events):
        if ev[0] != "install":
            continue
        for later in events[i + 1:]:
            # protection is judged at the CALL: if the raise lands
            # inside a try whose handler rolls back and re-raises, the
            # install is undone no matter where it was armed
            if later[0] == "call" and not later[3]:
                findings.append(Finding(
                    src.path, ev[1], RULE_INSTALL_LEAK,
                    "'%s.__init__' arms this global install and then "
                    "runs fallible '%s' with no rollback on the "
                    "raising path — a failed construction leaks the "
                    "install with no instance left to undo it; wrap "
                    "the tail in try/except that uninstalls (and "
                    "restores any prior state) before re-raising"
                    % (cls_name, later[2])))
                break
    return findings


# --------------------------------------------------------------------- #
# Analyzer plumbing                                                     #
# --------------------------------------------------------------------- #


def _analysis(ctx: LintContext) -> dict:
    bucket = ctx.bucket("ordering")
    if "order_findings" in bucket:
        return bucket
    an = _OrderAnalysis(ctx)
    an.run(ctx)
    bucket["order_findings"] = an.verify()
    bucket["contracts"] = set(an.contracts)
    bucket["events"] = set(an.events)
    return bucket


def _check_order(src: SourceFile, ctx: LintContext) -> list[Finding]:
    return []


def _finish_order(ctx: LintContext) -> list[Finding]:
    return list(_analysis(ctx)["order_findings"])


def static_order_table(root: str | None = None,
                       paths: tuple[str, ...] = ("opentsdb_tpu",)
                       ) -> dict:
    """{"contracts": {(a, b), ...}, "events": {name, ...}} — the static
    table tsdbsan's runtime order recorder cross-checks its per-trace
    event streams against (tools/sanitize/order.py), mirroring
    `blocking.static_request_paths`.  A line-regex scan, not a lint
    run: the cross-check only needs the declared NAMES, and it runs
    inside the sanitized session's wall-time budget — parsing the tree
    into ASTs there would eat the 2x overhead pin for nothing."""
    import os
    from tools.lint.core import REPO_ROOT
    base = root or REPO_ROOT
    contracts: set[tuple[str, str]] = set()
    events: set[str] = set()
    for top in paths:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(base, top)):
            dirnames[:] = [d for d in dirnames if not d.startswith(".")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                try:
                    with open(os.path.join(dirpath, fn), "r",
                              encoding="utf-8") as fh:
                        for line in fh:
                            if "# order" not in line:
                                continue
                            contracts.update(order_contracts(line))
                            events.update(order_events(line))
                except (OSError, UnicodeDecodeError):
                    continue
    return {"contracts": contracts, "events": events}


ORDER_ANALYZER = Analyzer(
    "order_contract", (RULE_ORDER,), _check_order, _finish_order)
ATOMICITY_ANALYZER = Analyzer(
    "failure_atomicity", (RULE_TORN, RULE_INSTALL_LEAK), _check_atomicity)
