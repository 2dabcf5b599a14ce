"""Cache-coherence & stale-state analysis: every cached artifact's
read-set mutation must reach its registered invalidator.

PR 6's review pass fixed three independent instances of one bug class —
global state mutated without dropping the caches derived from it (the
kernel-mode setters and calibration layers of the time, gone since
PR 29).  Stale caches in this codebase produce *wrong answers*, not
slow ones: a block served after its points were rewritten, a handler
left on the root logger.  This analyzer makes the invalidation
discipline a checked contract.

Model (three registries, one rule):

  cached artifacts
      * `functools.lru_cache` / `functools.cache` callables — the
        registered invalidator is `<fn>.cache_clear()`.
      * module-scope `X = jax.jit(fn, ...)` bindings (the jit mode
        caches in ops/) — the registered invalidator is
        `X.clear_cache()`; the cache READS whatever `fn` traces.
      * manual dict/attr caches declared with
        `# cache: <name> invalidated-by: <func>`
        (grammar in tools/lint/annotations.py).  Several declarations
        may share one cache name — a cache can have more than one
        backing global (table + bookkeeping set).  `invalidated-by:
        none` declares the read-set immutable; the analyzer verifies
        that no mutable state can reach it.

  read-set
      For each cached artifact, the transitive set of mutable module
      globals its reader functions consult (callgraph closure over
      bare-name / self / module-alias calls; attribute-devirtualized
      calls are deliberately excluded so read-sets stay tight).  A
      read of ANOTHER cache's backing global imports that cache's
      read-set instead (read-through): a mutation behind one cache
      obligates the invalidators of every cache reading through it.
      Mutable = assigned under a `global` declaration,
      or mutated in place (`.clear()/.update()/[k] = ...`) on a module
      global, anywhere in a function body.

  the coherence rule
      Every mutation site of a name in some cache's read-set must
      reach that cache's registered invalidator on the same
      non-exceptional path (statement walk in the resource_leak style:
      a `return` that crosses an undischarged obligation reports, and
      so does falling off the end).  Invalidators are recognized
      TRANSITIVELY through single entry points: a setter is coherent
      because it calls the one function that drops the dependent
      caches — so deleting the cache-drop inside the entry point
      fails every mutation site routed through it.
      Exemptions: `__init__` bodies (pre-publication construction),
      the cache's own backing globals (fills/drops are the
      invalidator's business, checked by the gutted rule below), and
      mutations inside a function that IS the cache's registered
      invalidator.

  paired installs
      `# global-install[: <uninstaller>] paired-with: <func>` marks a
      process-global install site (logging
      handlers, compile-log subscriptions, patched factories).  The
      pairing function must exist (same class, then module), must call
      the named uninstaller, and must be reachable from a
      shutdown/close/stop/__exit__ path.

Rules:

  cache-stale-mutation           a read-set mutation can finish (or
                                 early-return) without reaching the
                                 cache's invalidator
  cache-invalidator-gutted       a registered invalidator no longer
                                 drops any backing store of its cache
  cache-undeclared               a module-global dict used in the
                                 memo idiom (get-then-fill) with no
                                 `# cache:` declaration and no
                                 lru_cache
  cache-bad-annotation           a `# cache:` annotation that names no
                                 resolvable declaration/invalidator,
                                 or conflicting invalidators for one
                                 cache name
  install-missing-uninstall      pairing function absent, or it never
                                 calls the declared uninstaller
  install-unreachable-uninstall  pairing function exists but no
                                 shutdown/close/stop/__exit__ path
                                 reaches it
"""

from __future__ import annotations

import ast
import dataclasses

from tools.lint.annotations import cache_annotation, install_annotation
from tools.lint.callgraph import FuncInfo, get_callgraph
from tools.lint.core import Analyzer, Finding, LintContext, SourceFile

RULE_STALE = "cache-stale-mutation"
RULE_GUTTED = "cache-invalidator-gutted"
RULE_UNDECLARED = "cache-undeclared"
RULE_BAD_ANN = "cache-bad-annotation"
RULE_INSTALL_MISSING = "install-missing-uninstall"
RULE_INSTALL_UNREACHABLE = "install-unreachable-uninstall"

# receiver-method calls that mutate a module-global container in place
MUTATORS = frozenset({"clear", "update", "setdefault", "pop", "append",
                      "extend", "add", "remove", "discard", "insert",
                      "popitem"})
# tokens that clear a compiled-program / lru cache
CLEAR_METHODS = frozenset({"clear_cache", "cache_clear"})
# function names that anchor a shutdown/teardown path
SHUTDOWN_NAMES = frozenset({"shutdown", "close", "stop", "__exit__",
                            "__del__", "uninstall", "teardown"})
_LRU_NAMES = frozenset({"lru_cache", "cache"})

_FIXPOINT_MAX = 40


@dataclasses.dataclass
class CacheArtifact:
    name: str                      # display name (qname or annotation)
    kind: str                      # 'lru' | 'jit' | 'manual'
    module: str
    path: str
    line: int
    backing: set                   # {(module, global)} — empty for attr
    attr_backing: set              # {(class, attr)} for self.X caches
    readers: list                  # [FuncInfo]
    invalidator: str | None        # annotated func name, or None
    # (module, binding-name) tokens whose .clear_cache()/.cache_clear()
    # invalidates this cache (lru/jit kinds)
    tokens: set = dataclasses.field(default_factory=set)
    read_set: set = dataclasses.field(default_factory=set)
    # `invalidated-by: none` — read-set declared immutable; verified
    declared_none: bool = False
    # resolved FuncInfo of the registered invalidator, set in finish()
    invalidator_info: object = None


def check(src: SourceFile, ctx: LintContext) -> list[Finding]:
    # everything is whole-program: see finish()
    del src, ctx
    return []


# --------------------------------------------------------------------- #
# Per-module fact extraction                                            #
# --------------------------------------------------------------------- #

def _module_globals(tree: ast.Module) -> set[str]:
    out: set[str] = set()
    for st in tree.body:
        if isinstance(st, ast.Assign):
            for t in st.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
        elif isinstance(st, (ast.AnnAssign, ast.AugAssign)) and \
                isinstance(st.target, ast.Name):
            out.add(st.target.id)
    return out


def _decl_on_line(tree: ast.Module, lineno: int) -> tuple[str, int] | None:
    """The module-scope global declared on `lineno` or the next
    declaration after it (standalone annotation comment above)."""
    best = None
    for st in tree.body:
        if isinstance(st, ast.Assign) and len(st.targets) == 1 and \
                isinstance(st.targets[0], ast.Name):
            name, ln = st.targets[0].id, st.lineno
        elif isinstance(st, ast.AnnAssign) and \
                isinstance(st.target, ast.Name):
            name, ln = st.target.id, st.lineno
        else:
            continue
        if st.lineno <= lineno <= (st.end_lineno or st.lineno):
            return name, ln
        if st.lineno > lineno and (best is None or st.lineno < best[1]):
            best = (name, st.lineno)
    # a standalone comment annotates the declaration directly below it
    if best is not None and best[1] <= lineno + 2:
        return best
    return None


def _attr_decl_on_line(tree: ast.Module, lineno: int
                       ) -> tuple[str, str] | None:
    """(class, attr) when `lineno` declares a self.<attr> = ... inside a
    class body (attr-cache annotation).  Like `_decl_on_line`, a
    standalone comment annotates the declaration directly below it."""
    best = None
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self":
                    if node.lineno <= lineno <= (node.end_lineno or
                                                 node.lineno):
                        return cls.name, t.attr
                    if node.lineno > lineno and (
                            best is None or node.lineno < best[2]):
                        best = (cls.name, t.attr, node.lineno)
    if best is not None and best[2] <= lineno + 2:
        return best[0], best[1]
    return None


def _global_decls(fn: ast.AST) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Global):
            out.update(node.names)
    return out


def _lru_decorated(node) -> bool:
    for dec in node.decorator_list:
        d = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(d, ast.Name) and d.id in _LRU_NAMES:
            return True
        if isinstance(d, ast.Attribute) and d.attr in _LRU_NAMES:
            return True
    return False


class _Facts:
    """Everything finish() needs, computed once per LintContext."""

    def __init__(self, ctx: LintContext):
        self.cg = get_callgraph(ctx)
        self.files = {src.path: src for src in ctx.files}
        self.mod_globals: dict[str, set[str]] = {}
        self.mod_src: dict[str, SourceFile] = {}
        for src in ctx.files:
            from tools.lint.callgraph import module_name
            mod = module_name(src.path)
            self.mod_globals[mod] = _module_globals(src.tree)
            self.mod_src[mod] = src
        # (module, name) -> [(FuncInfo, stmt, line)]
        self.mutations: dict[tuple, list] = {}
        # funcqname -> {(module, name)} direct global reads
        self.reads: dict[str, set] = {}
        # funcqname -> [FuncInfo] resolved callees (restricted forms)
        self.callees: dict[str, list] = {}
        # funcqname -> {(module, binding)} cleared via token methods
        self.clear_tokens: dict[str, set] = {}
        # funcqname -> {(module, global)} dropped (None/clear/del)
        self.drops: dict[str, set] = {}
        # funcqname -> {(class, attr)} attr stores dropped
        self.attr_drops: dict[str, set] = {}
        for fi in self.cg.funcs.values():
            self._summarize(fi)

    # -- helpers ---------------------------------------------------------

    def _target_module(self, caller: FuncInfo, alias: str) -> str | None:
        mod = self.cg.modules.get(caller.module)
        if mod is None:
            return None
        tgt = mod.imports.get(alias)
        return tgt if tgt in self.cg.modules else None

    def _global_ref(self, caller: FuncInfo, node: ast.expr
                    ) -> tuple | None:
        """(module, name) when `node` names a module global: a bare
        Name of the caller's module, or alias.NAME of an imported
        module."""
        if isinstance(node, ast.Name):
            if node.id in self.mod_globals.get(caller.module, ()):
                return (caller.module, node.id)
            return None
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name):
            tgt = self._target_module(caller, node.value.id)
            if tgt and node.attr in self.mod_globals.get(tgt, ()):
                return (tgt, node.attr)
        return None

    def _binding_ref(self, caller: FuncInfo, node: ast.expr
                     ) -> tuple | None:
        """(module, binding) for a clear receiver: a bare Name in the
        caller's module, or alias.NAME of an imported module."""
        if isinstance(node, ast.Name):
            return (caller.module, node.id)
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name):
            tgt = self._target_module(caller, node.value.id)
            if tgt:
                return (tgt, node.attr)
        return None

    def clear_refs(self, fi: FuncInfo, root: ast.AST) -> set:
        """Every (module, binding) whose compiled/lru cache is cleared
        under `root`: direct `X.clear_cache()` / `X.cache_clear()`
        receivers plus each binding listed in the clear-loop idiom
        `for fn in (a, mod.b, ...): fn.clear_cache()`.  The ONE
        definition of clear recognition — the summary pass
        (_summarize) and the obligation walk (_ObligationWalk) both
        consume it, so they cannot drift."""
        out: set = set()
        for node in ast.walk(root):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in CLEAR_METHODS:
                ref = self._binding_ref(fi, node.func.value)
                if ref is not None:
                    out.add(ref)
            elif isinstance(node, ast.For) and \
                    isinstance(node.target, ast.Name) and \
                    isinstance(node.iter, (ast.Tuple, ast.List)):
                loopvar = node.target.id
                clears = any(
                    isinstance(sub, ast.Call) and
                    isinstance(sub.func, ast.Attribute) and
                    sub.func.attr in CLEAR_METHODS and
                    isinstance(sub.func.value, ast.Name) and
                    sub.func.value.id == loopvar
                    for st in node.body for sub in ast.walk(st))
                if not clears:
                    continue
                for el in node.iter.elts:
                    ref = self._binding_ref(fi, el)
                    if ref is not None:
                        out.add(ref)
        return out

    def _summarize(self, fi: FuncInfo) -> None:
        reads: set = set()
        callees: list = []
        tokens: set = set()
        drops: set = set()
        attr_drops: set = set()
        gdecls = _global_decls(fi.node)
        local_assigned = {
            t.id for st in ast.walk(fi.node)
            if isinstance(st, ast.Assign)
            for t in st.targets if isinstance(t, ast.Name)}
        params = set(fi.params)

        def is_global_name(name: str) -> bool:
            if name not in self.mod_globals.get(fi.module, ()):
                return False
            if name in gdecls:
                return True
            return name not in local_assigned and name not in params

        for node in ast.walk(fi.node):
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load) and \
                    is_global_name(node.id):
                reads.add((fi.module, node.id))
            elif isinstance(node, ast.Attribute) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.value, ast.Name):
                ref = self._global_ref(fi, node)
                if ref is not None:
                    reads.add(ref)
            elif isinstance(node, ast.Call):
                self._call_facts(fi, node, callees)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                self._assign_facts(fi, node, gdecls, drops, attr_drops)
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    base = tgt.value if isinstance(tgt, ast.Subscript) \
                        else tgt
                    if isinstance(base, ast.Name) and \
                            is_global_name(base.id):
                        ref = (fi.module, base.id)
                        drops.add(ref)
                        self._note_mutation(fi, ref, node)
        # direct clear calls + the clear-loop idiom, via the shared
        # recognizer the obligation walk also uses
        tokens |= self.clear_refs(fi, fi.node)
        # in-place container mutations + token loops
        self._mutation_facts(fi, gdecls, local_assigned, params)
        self.reads[fi.qname] = reads
        self.callees[fi.qname] = callees
        self.clear_tokens[fi.qname] = tokens
        # merge: _mutation_facts records `.clear()`-style drops directly
        self.drops.setdefault(fi.qname, set()).update(drops)
        self.attr_drops[fi.qname] = attr_drops

    def _call_facts(self, fi: FuncInfo, node: ast.Call,
                    callees: list) -> None:
        f = node.func
        # X.clear_cache() / X.cache_clear(): token collected by
        # clear_refs; never a callee to resolve
        if isinstance(f, ast.Attribute) and f.attr in CLEAR_METHODS:
            return
        # restricted resolution: bare names, self.m, alias.attr only —
        # unknown-receiver devirtualization would bloat read-sets with
        # every same-named method in the tree
        resolvable = isinstance(f, ast.Name)
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            resolvable = (f.value.id == "self"
                          or self._target_module(fi, f.value.id)
                          is not None)
        if resolvable:
            for info, _ctor, _cls in self.cg.resolve(node, fi):
                if info is not None:
                    callees.append(info)

    def _assign_facts(self, fi: FuncInfo, node, gdecls: set,
                      drops: set, attr_drops: set) -> None:
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id in gdecls:
                ref = (fi.module, t.id)
                self._note_mutation(fi, ref, node)
                if isinstance(node, ast.Assign) and \
                        _is_empty_value(node.value):
                    drops.add(ref)
            elif isinstance(t, ast.Subscript):
                base = t.value
                # subscript store into a module-global container
                if isinstance(base, ast.Name) and \
                        self._is_module_global_here(fi, base.id):
                    self._note_mutation(fi, (fi.module, base.id), node)
            elif isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and \
                    t.value.id == "self" and fi.klass is not None:
                if isinstance(node, ast.Assign) and \
                        _is_empty_value(node.value):
                    attr_drops.add((fi.klass, t.attr))

    def _is_module_global_here(self, fi: FuncInfo, name: str) -> bool:
        if name not in self.mod_globals.get(fi.module, ()):
            return False
        params = set(fi.params)
        if name in params:
            return False
        for st in ast.walk(fi.node):
            if isinstance(st, ast.Assign):
                for t in st.targets:
                    if isinstance(t, ast.Name) and t.id == name and \
                            name not in _global_decls(fi.node):
                        return False
        return True

    def _mutation_facts(self, fi: FuncInfo, gdecls: set,
                        local_assigned: set, params: set) -> None:
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr in MUTATORS):
                continue
            base = node.func.value
            if isinstance(base, ast.Name) and \
                    base.id in self.mod_globals.get(fi.module, ()) and \
                    base.id not in params and \
                    (base.id in gdecls or base.id not in local_assigned):
                ref = (fi.module, base.id)
                self._note_mutation(fi, ref, node)
                if node.func.attr in ("clear", "popitem"):
                    self.drops.setdefault(fi.qname, set()).add(ref)

    def _note_mutation(self, fi: FuncInfo, ref: tuple, node) -> None:
        self.mutations.setdefault(ref, []).append((fi, node))


def _is_empty_value(v: ast.expr) -> bool:
    """None / {} / [] / set() / dict() — a drop, not a fill."""
    if isinstance(v, ast.Constant) and v.value is None:
        return True
    if isinstance(v, (ast.Dict, ast.List, ast.Set)) and not getattr(
            v, "keys", getattr(v, "elts", None)):
        return True
    if isinstance(v, ast.Call) and isinstance(v.func, ast.Name) and \
            v.func.id in ("dict", "set", "list") and not v.args:
        return True
    return False


# --------------------------------------------------------------------- #
# Registry construction                                                 #
# --------------------------------------------------------------------- #

def _build_registry(facts: _Facts, findings: list[Finding]
                    ) -> list[CacheArtifact]:
    caches: list[CacheArtifact] = []
    by_name: dict[tuple, CacheArtifact] = {}     # (module, ann-name)
    for path, src in sorted(facts.files.items()):
        from tools.lint.callgraph import module_name
        mod = module_name(path)
        # 1. annotated manual caches
        for i, line in enumerate(src.lines, start=1):
            ann = cache_annotation(line)
            if ann is None:
                continue
            cname, invalidator = ann
            decl = _decl_on_line(src.tree, i)
            attr = None if decl else _attr_decl_on_line(src.tree, i)
            if decl is None and attr is None:
                findings.append(Finding(
                    path, i, RULE_BAD_ANN,
                    "cache annotation %r matches no module-global or "
                    "self-attribute declaration" % cname))
                continue
            key = (mod, cname)
            art = by_name.get(key)
            if art is None:
                art = CacheArtifact(cname, "manual", mod, path, i,
                                    set(), set(), [],
                                    None if invalidator == "none"
                                    else invalidator,
                                    declared_none=invalidator == "none")
                by_name[key] = art
                caches.append(art)
            elif (invalidator == "none") != art.declared_none or (
                    invalidator != "none" and
                    art.invalidator != invalidator):
                findings.append(Finding(
                    path, i, RULE_BAD_ANN,
                    "cache %r declares conflicting invalidators"
                    % cname))
            if decl is not None:
                art.backing.add((mod, decl[0]))
            else:
                art.attr_backing.add(attr)
        # 2. lru_cache functions + module-scope jax.jit bindings
        for fi in facts.cg.funcs.values():
            if fi.path != path:
                continue
            if _lru_decorated(fi.node):
                art = CacheArtifact(fi.qname, "lru", mod, path,
                                    fi.node.lineno, {(mod, fi.name)},
                                    set(), [fi], None,
                                    tokens={(mod, fi.name)})
                caches.append(art)
        for st in src.tree.body:
            if not (isinstance(st, ast.Assign) and len(st.targets) == 1
                    and isinstance(st.targets[0], ast.Name)
                    and isinstance(st.value, ast.Call)):
                continue
            f = st.value.func
            is_jit = (isinstance(f, ast.Attribute) and f.attr == "jit"
                      and isinstance(f.value, ast.Name)
                      and f.value.id == "jax") or \
                     (isinstance(f, ast.Name) and f.id == "jit")
            if not is_jit or not st.value.args:
                continue
            binding = st.targets[0].id
            reader = None
            arg0 = st.value.args[0]
            if isinstance(arg0, ast.Name):
                reader = facts.cg.modules[mod].functions.get(arg0.id)
            art = CacheArtifact("%s.%s" % (mod, binding), "jit", mod,
                                path, st.lineno, {(mod, binding)},
                                set(), [reader] if reader else [],
                                None, tokens={(mod, binding)})
            caches.append(art)
    # readers of manual caches: any function with a genuine READ of a
    # backing global.  A drop-only touch (`X.clear()`, `X.pop()`) does
    # NOT make a function a reader — otherwise every invalidator would
    # import its cache's read-set and read-through would manufacture
    # false dependency cycles through the invalidation entry points.
    for art in caches:
        if art.kind != "manual":
            continue
        for fi in facts.cg.funcs.values():
            for mod, name in art.backing:
                if mod == fi.module and _reads_name(fi.node, name):
                    art.readers.append(fi)
                    break
    return caches


_DROP_METHODS = frozenset({"clear", "pop", "popitem"})


def _reads_name(fn, name: str) -> bool:
    """A Load of `name` that is not merely the receiver of a drop call."""
    loads = drops = 0
    for n in ast.walk(fn):
        if isinstance(n, ast.Name) and n.id == name and \
                isinstance(n.ctx, ast.Load):
            loads += 1
        elif isinstance(n, ast.Call) and \
                isinstance(n.func, ast.Attribute) and \
                n.func.attr in _DROP_METHODS and \
                isinstance(n.func.value, ast.Name) and \
                n.func.value.id == name:
            drops += 1
    return loads > drops


# --------------------------------------------------------------------- #
# Read-set closure + invalidator relation                               #
# --------------------------------------------------------------------- #

def _transitive_reads(facts: _Facts) -> dict[str, set]:
    summary = {q: set(r) for q, r in facts.reads.items()}
    for _ in range(_FIXPOINT_MAX):
        changed = False
        for q, callees in facts.callees.items():
            s = summary.setdefault(q, set())
            before = len(s)
            for c in callees:
                s |= summary.get(c.qname, set())
            changed |= len(s) != before
        if not changed:
            break
    return summary


def _resolve_invalidator(facts: _Facts, art: CacheArtifact
                         ) -> FuncInfo | None:
    name = art.invalidator
    if not name:
        return None
    mod = facts.cg.modules.get(art.module)
    if mod is None:
        return None
    head, _, tail = name.rpartition(".")
    if head:
        tgt = mod.imports.get(head, head)
        other = facts.cg.modules.get(tgt)
        if other is not None and tail in other.functions:
            return other.functions[tail]
        # Class.method in the same module
        fi = facts.cg.class_method(art.module, head, tail)
        if fi is not None:
            return fi
        return None
    if name in mod.functions:
        return mod.functions[name]
    # a method: any class in the module defining it
    for cls in mod.classes:
        fi = mod.classes[cls].get(name)
        if fi is not None:
            return fi
    tgt = mod.imports.get(name)
    if tgt:
        sym = facts.cg._symbol(tgt)
        if isinstance(sym, FuncInfo):
            return sym
    return None


def _drops_cache(facts: _Facts, art: CacheArtifact, start: FuncInfo,
                 depth: int = 4) -> bool:
    """True when `start` (transitively) drops one of the cache's
    backing stores or clears one of its tokens."""
    seen: set[str] = set()
    stack = [(start, 0)]
    while stack:
        fi, d = stack.pop()
        if fi.qname in seen or d > depth:
            continue
        seen.add(fi.qname)
        if facts.drops.get(fi.qname, set()) & art.backing:
            return True
        if facts.attr_drops.get(fi.qname, set()) & art.attr_backing:
            return True
        if facts.clear_tokens.get(fi.qname, set()) & art.tokens:
            return True
        for c in facts.callees.get(fi.qname, ()):
            stack.append((c, d + 1))
    return False


def _invalidator_funcs(facts: _Facts, caches: list[CacheArtifact],
                       findings: list[Finding]) -> dict[str, set]:
    """qname -> set of cache ids the function (transitively)
    invalidates.  Manual caches are single-entry-point: only the
    registered invalidator (and its transitive callers) count, and a
    registered invalidator that no longer drops its backing store is a
    `cache-invalidator-gutted` finding."""
    direct: dict[str, set] = {}
    for idx, art in enumerate(caches):
        if art.kind == "manual":
            if art.invalidator is None:     # invalidated-by: none
                continue
            inv = _resolve_invalidator(facts, art)
            if inv is None:
                findings.append(Finding(
                    art.path, art.line, RULE_BAD_ANN,
                    "cache %r names invalidator %r which resolves to "
                    "no scanned function" % (art.name, art.invalidator)))
                continue
            art.invalidator_info = inv
            if not _drops_cache(facts, art, inv):
                findings.append(Finding(
                    inv.path, inv.node.lineno, RULE_GUTTED,
                    "'%s' is the registered invalidator of cache %r "
                    "but no longer drops any of its backing stores "
                    "(%s)" % (inv.name, art.name,
                              ", ".join(sorted(n for _m, n
                                               in art.backing)) or
                              ", ".join(sorted("self.%s" % a
                                               for _c, a in
                                               art.attr_backing))))
                )
            direct.setdefault(inv.qname, set()).add(idx)
        else:
            for q, tokens in facts.clear_tokens.items():
                if tokens & art.tokens:
                    direct.setdefault(q, set()).add(idx)
    # transitive closure: F invalidates whatever its callees invalidate
    inval = {q: set(s) for q, s in direct.items()}
    for _ in range(_FIXPOINT_MAX):
        changed = False
        for q, callees in facts.callees.items():
            s = inval.setdefault(q, set())
            before = len(s)
            for c in callees:
                s |= inval.get(c.qname, set())
            changed |= len(s) != before
        if not changed:
            break
    return inval


# --------------------------------------------------------------------- #
# The path walk: mutation must reach invalidator                        #
# --------------------------------------------------------------------- #

class _ObligationWalk:
    """One function, one cache: walk the statement list tracking
    undischarged mutation obligations (resource_leak style).  A
    `return` crossing a pending obligation reports; raises are
    exceptional exits and out of scope.  Discharge is branch-aware
    for `if`: a clear inside one branch counts only when every branch
    clears (or exits exceptionally) — a conditionally-skipped
    invalidation is exactly the bug class.  Loop and try bodies stay
    optimistic (a clear anywhere inside counts), documented in
    docs/static_analysis.md."""

    def __init__(self, facts: _Facts, fi: FuncInfo, cache_idx: int,
                 inval: dict[str, set], mutation_nodes: list,
                 cache_name: str, path: str):
        self.facts = facts
        self.fi = fi
        self.idx = cache_idx
        self.inval = inval
        self.mutations = {id(n): n for n in mutation_nodes}
        self.cache_name = cache_name
        self.path = path
        self.pending: dict[int, object] = {}
        self.findings: list[Finding] = []

    def _discharges(self, st: ast.stmt) -> bool:
        # direct clears + the clear-loop idiom, via the same recognizer
        # _Facts._summarize feeds the invalidator summaries from
        if any(ref in self._tokens
               for ref in self.facts.clear_refs(self.fi, st)):
            return True
        for node in ast.walk(st):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in CLEAR_METHODS:
                continue    # handled by clear_refs above
            if isinstance(f, ast.Name) or (
                    isinstance(f, ast.Attribute) and
                    isinstance(f.value, ast.Name)):
                for info, _c, _n in self.facts.cg.resolve(node, self.fi):
                    if info is not None and self.idx in \
                            self.inval.get(info.qname, set()):
                        return True
        return False

    def _stmt_discharges(self, st: ast.stmt) -> bool:
        """Branch-aware discharge for one statement."""
        if isinstance(st, ast.If):
            return (self._branch_discharges(st.body) and bool(st.orelse)
                    and self._branch_discharges(st.orelse))
        if isinstance(st, ast.With):
            return self._branch_discharges(st.body)
        return self._discharges(st)

    def _branch_discharges(self, stmts) -> bool:
        for s in stmts:
            if isinstance(s, ast.Raise):
                return True       # exceptional exit — out of scope
            if self._stmt_discharges(s):
                return True
        return False

    def run(self, tokens: set) -> list[Finding]:
        self._tokens = tokens
        self._walk(self.fi.node.body, False)
        for mid, node in self.pending.items():
            self._report(node)
        return self.findings

    def _report(self, node) -> None:
        self.findings.append(Finding(
            self.path, node.lineno, RULE_STALE,
            "mutation in '%s' is in the read-set of cache %r but no "
            "non-exceptional path from it reaches the cache's "
            "invalidator — stale entries will keep serving the old "
            "state" % (self.fi.name, self.cache_name)))

    def _walk(self, stmts, protected: bool) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if self._stmt_discharges(st):
                self.pending.clear()
            if isinstance(st, ast.Return):
                for mid, node in list(self.pending.items()):
                    if not protected:
                        self._report(node)
                    self.pending.pop(mid)
                continue
            if isinstance(st, ast.Raise):
                self.pending.clear()      # exceptional exit: out of scope
                continue
            if isinstance(st, ast.Try):
                fin_discharges = any(self._discharges(f)
                                     for f in st.finalbody)
                self._walk(st.body, protected or fin_discharges)
                for h in st.handlers:
                    self._walk(h.body, protected or fin_discharges)
                self._walk(st.orelse, protected or fin_discharges)
                self._walk(st.finalbody, protected)
                if fin_discharges:
                    self.pending.clear()
                continue
            if isinstance(st, (ast.If, ast.While, ast.For)):
                self._walk(st.body, protected)
                self._walk(st.orelse, protected)
            elif isinstance(st, ast.With):
                self._walk(st.body, protected)
            # activate obligations declared by THIS statement (after
            # discharge: `x = v` and the invalidating call never share
            # a statement in the idiom this checks)
            for node in ast.walk(st):
                if id(node) in self.mutations:
                    self.pending[id(node)] = node
                    self.mutations.pop(id(node), None)


# --------------------------------------------------------------------- #
# finish: the whole-program pass                                        #
# --------------------------------------------------------------------- #

def finish(ctx: LintContext) -> list[Finding]:
    if not ctx.files:
        return []
    findings: list[Finding] = []
    facts = _Facts(ctx)
    caches = _build_registry(facts, findings)
    summaries = _transitive_reads(facts)

    backing_of: dict[tuple, int] = {}
    for idx, art in enumerate(caches):
        for ref in art.backing:
            backing_of.setdefault(ref, idx)
    all_backing = set(backing_of)

    mutable = set(facts.mutations) - all_backing

    # raw read-sets, then read-through backing names of other caches
    for art in caches:
        rs: set = set()
        for fi in art.readers:
            rs |= summaries.get(fi.qname, set())
        art.read_set = rs
    for _ in range(_FIXPOINT_MAX):
        changed = False
        for idx, art in enumerate(caches):
            for ref in list(art.read_set & all_backing):
                other = backing_of[ref]
                if other != idx:
                    before = len(art.read_set)
                    art.read_set |= caches[other].read_set - all_backing
                    changed |= len(art.read_set) != before
        if not changed:
            break
    for art in caches:
        art.read_set = (art.read_set - all_backing) & mutable

    inval = _invalidator_funcs(facts, caches, findings)

    # the coherence rule
    for ref in sorted(mutable):
        interested = [i for i, a in enumerate(caches)
                      if ref in a.read_set]
        if not interested:
            continue
        for fi, node in facts.mutations[ref]:
            if fi.name == "__init__":
                continue        # pre-publication construction
            for i in interested:
                art = caches[i]
                if art.kind == "manual" and art.invalidator is None:
                    findings.append(Finding(
                        fi.path, node.lineno, RULE_STALE,
                        "mutation in '%s' reaches cache %r which is "
                        "declared `invalidated-by: none` (immutable "
                        "read-set) — declare a real invalidator or "
                        "remove the mutable dependency"
                        % (fi.name, art.name)))
                    continue
                if art.kind == "manual" and art.invalidator_info is fi:
                    continue    # the invalidator's own bookkeeping
                if self_invalidates(fi, i, inval):
                    walk = _ObligationWalk(
                        facts, fi, i, inval,
                        [node], art.name, fi.path)
                    findings.extend(walk.run(art.tokens))
                else:
                    findings.append(Finding(
                        fi.path, node.lineno, RULE_STALE,
                        "'%s' mutates state in the read-set of cache "
                        "%r but never reaches its invalidator%s"
                        % (fi.name, art.name,
                           " ('%s')" % art.invalidator
                           if art.invalidator else "")))

    findings.extend(_undeclared_memos(facts, caches))
    findings.extend(_check_installs(facts))
    return findings


def self_invalidates(fi: FuncInfo, idx: int,
                     inval: dict[str, set]) -> bool:
    return idx in inval.get(fi.qname, set())


# --------------------------------------------------------------------- #
# Undeclared memo caches                                                #
# --------------------------------------------------------------------- #

def _undeclared_memos(facts: _Facts,
                      caches: list[CacheArtifact]) -> list[Finding]:
    declared = set()
    for art in caches:
        declared |= art.backing
    out: list[Finding] = []
    for mod, src in sorted(facts.mod_src.items()):
        for st in src.tree.body:
            if isinstance(st, ast.Assign) and len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name):
                name, value = st.targets[0].id, st.value
            elif isinstance(st, ast.AnnAssign) and \
                    isinstance(st.target, ast.Name) and \
                    st.value is not None:
                name, value = st.target.id, st.value
            else:
                continue
            if not (isinstance(value, ast.Dict) and not value.keys) and \
               not (isinstance(value, ast.Call) and
                    isinstance(value.func, ast.Name) and
                    value.func.id == "dict" and not value.args):
                continue
            if (mod, name) in declared:
                continue
            filled = read = False
            for fi in facts.cg.funcs.values():
                if fi.module != mod:
                    continue
                for node in ast.walk(fi.node):
                    if isinstance(node, ast.Assign):
                        for t in node.targets:
                            if isinstance(t, ast.Subscript) and \
                                    isinstance(t.value, ast.Name) and \
                                    t.value.id == name:
                                filled = True
                    elif isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "get" and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id == name:
                        read = True
                    elif isinstance(node, ast.Compare) and \
                            any(isinstance(op, (ast.In, ast.NotIn))
                                for op in node.ops) and \
                            isinstance(node.comparators[-1], ast.Name) \
                            and node.comparators[-1].id == name:
                        read = True
            if filled and read:
                out.append(Finding(
                    src.path, st.lineno, RULE_UNDECLARED,
                    "module global %r is used as a memo cache "
                    "(get-then-fill) but declares no invalidator — "
                    "add `# cache: <name> invalidated-by: <func>` "
                    "(or `none` for an immutable read-set)" % name))
    return out


# --------------------------------------------------------------------- #
# Paired global installs                                                #
# --------------------------------------------------------------------- #

def _enclosing_func(facts: _Facts, path: str, line: int
                    ) -> FuncInfo | None:
    best = None
    for fi in facts.cg.funcs.values():
        if fi.path != path:
            continue
        if fi.node.lineno <= line <= (fi.node.end_lineno or 10 ** 9):
            if best is None or fi.node.lineno > best.node.lineno:
                best = fi
    return best


def _calls_name(facts: _Facts, start: FuncInfo, target: str,
                depth: int = 3) -> bool:
    """Does `start` (transitively, depth-bounded) contain a call whose
    terminal name is `target`?"""
    seen: set[str] = set()
    stack = [(start, 0)]
    while stack:
        fi, d = stack.pop()
        if fi.qname in seen or d > depth:
            continue
        seen.add(fi.qname)
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else \
                f.attr if isinstance(f, ast.Attribute) else None
            if name == target:
                return True
        for c in facts.callees.get(fi.qname, ()):
            stack.append((c, d + 1))
    return False


def _shutdown_reachable(facts: _Facts) -> set[str]:
    """qnames reachable (as callees) from any shutdown-named function,
    plus the shutdown-named functions themselves."""
    out: set[str] = set()
    stack = [fi for fi in facts.cg.funcs.values()
             if fi.name in SHUTDOWN_NAMES]
    out |= {fi.qname for fi in stack}
    while stack:
        fi = stack.pop()
        for c in facts.callees.get(fi.qname, ()):
            if c.qname not in out:
                out.add(c.qname)
                stack.append(c)
    return out


def _check_installs(facts: _Facts) -> list[Finding]:
    out: list[Finding] = []
    reachable = None
    for path, src in sorted(facts.files.items()):
        for i, line in enumerate(src.lines, start=1):
            ann = install_annotation(line)
            if ann is None:
                continue
            uninstaller, paired = ann
            fi = _enclosing_func(facts, path, i)
            # resolve the pairing function: same class, then module
            target = None
            if fi is not None and fi.klass is not None:
                target = facts.cg.class_method(fi.module, fi.klass,
                                               paired.split(".")[-1])
            if target is None and fi is not None:
                mod = facts.cg.modules.get(fi.module)
                if mod is not None:
                    target = mod.functions.get(paired)
            if target is None:
                # any scanned class defining the method (cross-class
                # pairings: the installer and the owner differ)
                cands = facts.cg.methods_by_name.get(
                    paired.split(".")[-1], [])
                if len(cands) == 1:
                    target = cands[0]
            if target is None:
                out.append(Finding(
                    path, i, RULE_INSTALL_MISSING,
                    "global install pairs with %r which resolves to no "
                    "scanned function — the install has no uninstall"
                    % paired))
                continue
            if uninstaller is not None and not _calls_name(
                    facts, target, uninstaller.split(".")[-1]):
                out.append(Finding(
                    path, i, RULE_INSTALL_MISSING,
                    "pairing function '%s' never calls the declared "
                    "uninstaller '%s' — the global install leaks past "
                    "it" % (target.name, uninstaller)))
                continue
            if reachable is None:
                reachable = _shutdown_reachable(facts)
            if target.name not in SHUTDOWN_NAMES and \
                    target.qname not in reachable:
                out.append(Finding(
                    path, i, RULE_INSTALL_UNREACHABLE,
                    "pairing function '%s' is not reachable from any "
                    "shutdown/close/stop/__exit__ path — the uninstall "
                    "exists but nothing runs it" % target.name))
    return out


ANALYZER = Analyzer(
    "cache_coherence",
    (RULE_STALE, RULE_GUTTED, RULE_UNDECLARED, RULE_BAD_ANN,
     RULE_INSTALL_MISSING, RULE_INSTALL_UNREACHABLE),
    check, finish)
