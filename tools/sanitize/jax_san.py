"""JAX compile/sync sanitizer: recompiles after warmup, host syncs.

High-throughput aggregation engines gate performance on ZERO hidden
recompiles and zero accidental device->host round-trips on the hot
query path.  tsdblint's jax_hygiene analyzer proves the *shape* of the
code (no per-call jit construction, no `.item()` on traced values);
this module proves the *behavior*:

  compile accounting   subscribes to the SHARED compile-log capture
        (opentsdb_tpu/obs/jaxprof.py CompileLogCapture — the same
        event stream tsdbobs's per-kernel compile counters consume, so
        the profiler and the sanitizer cannot drift).  The capture owns
        `jax_log_compiles` and the pxla "Compiling <kernel> ..."
        logging handler.  The run has two phases: warmup (compiles are
        expected and counted) and steady (entered via `mark_steady()`).
        Any compile event in steady state is a finding
        (san-recompile-after-warmup) attributed to the repo call site
        that triggered it — subscribers run synchronously in the
        compiling thread, so the stack still shows who asked.
  host-sync accounting  ArrayImpl's device->host surfaces (`__array__`,
        `item`, `tolist`, `__float__`, `__int__`, `__bool__`,
        `__index__`) are wrapped.  In steady state a transfer outside a
        sanctioned site is a finding (san-host-sync).  Sanctioned =
        inside a `sanctioned()` context, or any stack frame matching
        the SANCTIONED_SITES registry (the serialization boundary is
        where results legitimately leave the device).
  cache-size pinning    `snapshot_kernel_caches()` records
        `_cache_size()` of every module-scope jitted kernel in ops/ +
        parallel/; `check_cache_growth(snapshot)` reports kernels whose
        cache grew — per-kernel attribution that survives even when log
        capture is off.

Everything installs lazily and restores on stop(); with the sanitizer
off this module costs nothing.
"""

from __future__ import annotations

import sys
import threading

from opentsdb_tpu.obs.jaxprof import compile_capture
from tools.sanitize.report import REPORTER, caller_site

# (path suffix, function-name prefix) pairs whose presence anywhere on
# the stack sanctions a host sync: the serialization boundary and the
# planner's explicit result materialization are where query results are
# SUPPOSED to leave the device.  Keep this list short and justified —
# every entry is a hole in the detector.
SANCTIONED_SITES: list[tuple[str, str]] = [
    ("opentsdb_tpu/tsd/serializers.py", ""),
    ("opentsdb_tpu/query/planner.py", "_materialize"),
    ("opentsdb_tpu/ops/hostlane.py", ""),
]

_tls = threading.local()


class sanctioned:
    """`with jax_san.sanctioned():` — host syncs inside are expected."""

    def __enter__(self):
        _tls.depth = getattr(_tls, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.depth = getattr(_tls, "depth", 1) - 1


def _in_sanctioned_context() -> bool:
    return getattr(_tls, "depth", 0) > 0


def _at_sanctioned_site() -> bool:
    f = sys._getframe(2)
    hops = 0
    while f is not None and hops < 40:
        fn = f.f_code.co_filename.replace("\\", "/")
        for suffix, func_prefix in SANCTIONED_SITES:
            if fn.endswith(suffix) and \
                    f.f_code.co_name.startswith(func_prefix):
                return True
        f = f.f_back
        hops += 1
    return False


class JaxSanitizer:
    """One installable instance (tools/sanitize/install.py owns it)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()   # captured pre-patch via import time
        self.phase = "warmup"
        self.compiles: dict[str, dict[str, int]] = {}
        self.host_syncs: dict[str, int] = {}
        self._subscribed = False
        self._array_patches: list[tuple[type, str, object]] = []

    # -- lifecycle --

    def start(self) -> None:
        self.phase = "warmup"
        if not self._subscribed:
            # the shared capture (obs/jaxprof.py) owns jax_log_compiles
            # and the pxla handler; this instance just subscribes
            compile_capture.subscribe(self._on_compile)
            self._subscribed = True
        self._patch_array_type()

    def stop(self) -> None:
        if self._subscribed:
            compile_capture.unsubscribe(self._on_compile)
            self._subscribed = False
        for cls, name, orig in self._array_patches:
            setattr(cls, name, orig)
        self._array_patches = []

    def reset(self) -> None:
        with self._lock:
            self.phase = "warmup"
            self.compiles.clear()
            self.host_syncs.clear()

    def mark_steady(self) -> None:
        self.phase = "steady"

    # -- compile accounting --

    def _on_compile(self, kernel: str) -> None:
        with self._lock:
            per = self.compiles.setdefault(kernel,
                                           {"warmup": 0, "steady": 0})
            per[self.phase] += 1
            steady = self.phase == "steady"
        if steady:
            path, line, func = caller_site(skip=2)
            REPORTER.add(
                path, line, "san-recompile-after-warmup",
                "kernel '%s' compiled during steady state (triggered "
                "from '%s') — a hot serving path is recompiling after "
                "warmup" % (kernel, func))

    # -- host-sync accounting --

    def _patch_array_type(self) -> None:
        import jax.numpy as jnp
        cls = type(jnp.asarray(0))
        for name in ("__array__", "item", "tolist", "__float__",
                     "__int__", "__bool__", "__index__"):
            orig = getattr(cls, name, None)
            if orig is None:
                continue
            wrapper = self._make_sync_wrapper(name, orig)
            try:
                setattr(cls, name, wrapper)
            except (AttributeError, TypeError):
                continue
            self._array_patches.append((cls, name, orig))

    def _make_sync_wrapper(self, name: str, orig):
        san = self

        def _wrapped(array_self, *args, **kwargs):
            san._on_host_sync(name)
            return orig(array_self, *args, **kwargs)

        _wrapped.__name__ = name
        return _wrapped

    def _on_host_sync(self, surface: str) -> None:
        if self.phase != "steady":
            return
        if _in_sanctioned_context() or _at_sanctioned_site():
            return
        path, line, func = caller_site(skip=2)
        with self._lock:
            self.host_syncs[path] = self.host_syncs.get(path, 0) + 1
        REPORTER.add(
            path, line, "san-host-sync",
            "device->host transfer (%s) in '%s' during steady state, "
            "outside every sanctioned site — a hidden sync on the hot "
            "path" % (surface, func))


# --------------------------------------------------------------------- #
# Module-scope jitted kernel cache pinning                              #
# --------------------------------------------------------------------- #

KERNEL_MODULE_PREFIXES = ("opentsdb_tpu.ops.", "opentsdb_tpu.parallel.")


def snapshot_kernel_caches() -> dict[str, int]:
    """{qualified kernel name: jit cache size} for every module-scope
    jitted binding in the loaded ops/ + parallel/ modules."""
    out: dict[str, int] = {}
    for modname, mod in sorted(sys.modules.items()):
        if mod is None or not modname.startswith(KERNEL_MODULE_PREFIXES):
            continue
        for attr, value in sorted(vars(mod).items()):
            size_fn = getattr(value, "_cache_size", None)
            if callable(size_fn):
                try:
                    out["%s.%s" % (modname, attr)] = int(size_fn())
                except Exception:       # noqa: BLE001
                    continue
    return out


def check_cache_growth(before: dict[str, int]) -> list[str]:
    """Kernels whose jit cache grew since `before`; each one reports
    san-recompile-after-warmup with per-kernel attribution."""
    grown = []
    after = snapshot_kernel_caches()
    for kernel in sorted(before):
        if after.get(kernel, 0) > before[kernel]:
            grown.append(kernel)
            modname = kernel.rsplit(".", 1)[0]
            mod = sys.modules.get(modname)
            path = getattr(mod, "__file__", "<unknown>") or "<unknown>"
            from tools.sanitize.report import rel_path
            REPORTER.add(
                rel_path(path), 0, "san-recompile-after-warmup",
                "jitted kernel %s cache grew %d -> %d across the steady "
                "phase — a new shape/dtype reached a warm kernel"
                % (kernel, before[kernel], after.get(kernel, 0)))
    return grown
