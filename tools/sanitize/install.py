"""tsdbsan installation: patching orchestration + import hook.

`install()` arms the detectors process-wide:

  1. threading.Lock/RLock factories are swapped (tools/sanitize/locks)
     so lock constructions INSIDE the sanitized packages yield
     instrumented wrappers;
  2. every already-loaded `opentsdb_tpu.*` module is scanned with the
     shared annotation parser and its lock-holding classes get the
     write-interception layer (tools/sanitize/lockset);
  3. a meta-path hook instruments modules imported LATER the same way —
     lazy imports (the parallel/ mesh path, plugins) are covered
     without importing anything eagerly;
  4. the deadlock watchdog starts (tools/sanitize/deadlock) and the
     runtime ordering recorder arms (tools/sanitize/order) — the same
     module scan wraps the patch-table methods that realise tagged
     order events — as does the explain effect sentinel
     (tools/sanitize/effects): dispatch gateways, the admission
     permit, and the `explain_query` arming wrapper;
  5. optionally the JAX compile/sync sanitizer attaches
     (tools/sanitize/jax_san) — off by default under pytest, where
     compiles happen throughout; the steady-state serving check and
     the daemon mode turn it on.

`uninstall()` restores everything it patched.  Already-constructed
locks stay wrapped (they are real locks underneath and behave
identically); already-instrumented classes are restored.
"""

from __future__ import annotations

import importlib.machinery
import os
import sys

from tools.lint.core import REPO_ROOT

DEFAULT_PACKAGES = ("opentsdb_tpu",)

_installed: dict | None = None


def installed() -> bool:
    return _installed is not None


def install(lockset: bool = True, deadlock_watch: bool = True,
            jax: bool = False, watchdog_ms: int = 200,
            packages: tuple[str, ...] = DEFAULT_PACKAGES,
            extra_lock_prefixes: tuple[str, ...] = ()) -> None:
    """Idempotent; a second install() is a no-op."""
    global _installed
    if _installed is not None:
        return
    from tools.sanitize import deadlock, effects, jax_san, locks
    from tools.sanitize import lockset as ls
    from tools.sanitize import order
    lock_prefixes = tuple(packages) + tuple(extra_lock_prefixes)
    locks.patch_factories(lock_prefixes)
    ls.configure(lockset_enabled=lockset)
    deadlock.configure(enabled=deadlock_watch, watchdog_ms=watchdog_ms)
    order.configure(enabled=True)
    effects.configure(enabled=True)
    instrumented: list[type] = []
    for modname in sorted(sys.modules):
        if _in_packages(modname, packages):
            instrumented.extend(instrument_module(sys.modules[modname]))
    hook = _SanImportHook(packages)
    sys.meta_path.insert(0, hook)
    jsan = None
    if jax:
        jsan = jax_san.JaxSanitizer()
        jsan.start()
    _installed = {
        "hook": hook,
        "classes": instrumented,
        "jax": jsan,
        "packages": packages,
    }


def uninstall() -> None:
    global _installed
    if _installed is None:
        return
    from tools.sanitize import deadlock, effects, locks
    from tools.sanitize import lockset as ls
    from tools.sanitize import order
    state, _installed = _installed, None
    try:
        sys.meta_path.remove(state["hook"])
    except ValueError:
        pass
    for cls in state["classes"]:
        ls.uninstrument_class(cls)
    if state["jax"] is not None:
        state["jax"].stop()
    deadlock.configure(enabled=False)
    order.configure(enabled=False)
    order.unpatch_all()
    effects.configure(enabled=False)
    effects.unpatch_all()
    locks.unpatch_factories()


def jax_sanitizer():
    """The active JaxSanitizer, or None when jax accounting is off."""
    return _installed["jax"] if _installed else None


def reset_state() -> None:
    """Drop accumulated detector state (not the patches): fixture tests
    isolate scenarios with this."""
    from tools.sanitize import deadlock, effects, lockset as ls
    from tools.sanitize import order
    from tools.sanitize.report import REPORTER
    deadlock.reset()
    ls.reset()
    order.reset()
    effects.reset()
    REPORTER.clear()
    if _installed and _installed["jax"] is not None:
        _installed["jax"].reset()


def _in_packages(modname: str, packages: tuple[str, ...]) -> bool:
    return any(modname == p or modname.startswith(p + ".")
               for p in packages)


def instrument_module(mod) -> list[type]:
    """Scan one loaded module's SOURCE with the shared annotation
    parser and instrument its lock-holding classes.  Public so fixture
    tests can instrument tests/san_fixtures modules explicitly."""
    from tools.lint.annotations import scan_module_file
    from tools.sanitize import effects
    from tools.sanitize import lockset as ls
    from tools.sanitize import order
    order.instrument_module(mod)
    effects.instrument_module(mod)
    path = getattr(mod, "__file__", None)
    if not path or not path.endswith(".py") or not os.path.exists(path):
        return []
    try:
        rel = os.path.relpath(path, REPO_ROOT).replace(os.sep, "/")
        anns = scan_module_file(path, rel)
    except (OSError, SyntaxError, UnicodeDecodeError):
        return []
    out: list[type] = []
    for name, ann in sorted(anns.items()):
        if not ann.locks:
            continue
        cls = getattr(mod, name, None)
        if not isinstance(cls, type) or \
                getattr(cls, "__module__", None) != mod.__name__:
            continue
        if ls.instrument_class(cls, ann):
            out.append(cls)
    return out


class _SanImportHook:
    """Meta-path finder that lets the normal machinery find the module,
    then instruments it right after execution."""

    def __init__(self, packages: tuple[str, ...]) -> None:
        self._packages = packages

    def find_spec(self, fullname, path=None, target=None):
        if not _in_packages(fullname, self._packages):
            return None
        try:
            spec = importlib.machinery.PathFinder.find_spec(fullname, path)
        except (ImportError, ValueError):
            return None
        if spec is None or spec.loader is None:
            return None
        spec.loader = _WrappingLoader(spec.loader)
        return spec


class _WrappingLoader:
    def __init__(self, inner) -> None:
        self._inner = inner

    def create_module(self, spec):
        create = getattr(self._inner, "create_module", None)
        return create(spec) if create else None

    def exec_module(self, module) -> None:
        self._inner.exec_module(module)
        try:
            state = _installed
            if state is not None:
                state["classes"].extend(instrument_module(module))
        except Exception:       # noqa: BLE001 — never break an import
            pass

    def __getattr__(self, name):
        return getattr(self._inner, name)
