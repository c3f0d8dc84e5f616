"""Per-process JAX set-up: the platform pin and the compile cache.

The TPU chip is a process-exclusive resource: only one process per host may
own it (libtpu acquires it at backend init). The reference handles GPU
visibility with ``CUDA_VISIBLE_DEVICES`` injection in the raylet worker pool
(``python/ray/_private/accelerators``); the TPU analog is pinning the JAX
platform per worker: the node agent starts every worker that serves no
``TPU`` grant with ``RAY_TPU_JAX_PLATFORM=cpu`` (``node.worker_spawn_env``),
and the one TPU-granted worker gets the chip.

``RAY_TPU_JAX_PLATFORM`` wins over an inherited ``JAX_PLATFORMS``: a
post-import hook applies ``jax.config.update("jax_platforms", ...)`` the
moment jax is imported — paying zero cost in workers that never touch jax.

Every process of a session passes through :func:`install_hook` before it
imports jax, so this is also the one place that decides where XLA's
persistent compile cache lives (:func:`compile_cache_dir`), and the one
place that puts jax's compile path on the flight recorder
(:func:`record_program_builds`).
"""

from __future__ import annotations

import importlib.abc
import importlib.util
import os
import sys
import threading
import time
from typing import Optional

from ray_tpu.util import events

ENV_VAR = "RAY_TPU_JAX_PLATFORM"
CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where this session's processes keep compiled programs.

    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
    fixed ``<checkout>/.jax_cache``: the path is part of the cache key, so
    it is never derived from a temporary name, a pid or the time."""
    return os.environ.get(CACHE_ENV_VAR) or os.path.join(
        _CHECKOUT, ".jax_cache")


def _place_compile_cache():
    """Export the cache placement so jax reads it at import, here and in
    every child. The minimum compile time drops to zero (the minimum entry
    size already is): the paged engine's page scatter and the optimizer's
    small programs compile in well under jax's default one-second floor
    and would otherwise be rebuilt by every process."""
    os.environ.setdefault(CACHE_ENV_VAR, compile_cache_dir())
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if "jax" in sys.modules:
        # jax read its environment at import; a driver that imported it
        # before ray_tpu gets the same placement through the config.
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.environ[CACHE_ENV_VAR])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))


def apply(platform: str | None = None):
    """Apply the platform to an already-imported (or importable) jax."""
    platform = platform or os.environ.get(ENV_VAR)
    if not platform:
        return
    import jax

    jax.config.update("jax_platforms", platform)


# a compile and a load from the persistent cache alike
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 _BACKEND_EVENT)
# A step program's trace holds thousands of nested traces of microseconds
# each (4 786 of a replica's 4 866 rows: PERF.md section 6, PR 41): a
# trace or a lowering shorter than this writes no row; the backend's
# event always does.
_MIN_BUILD_ROW_S = 1e-3
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# whether the program this thread is building came from the persistent
# cache: jax says so in the same thread, just before the backend's event
_building = threading.local()
_recording = False


def _on_cache_event(event: str, **_):
    if event == _CACHE_HIT:
        _building.cache_hit = True
    elif event == _CACHE_MISS:
        _building.cache_hit = False


def _on_build_event(event: str, duration_secs: float, **kw):
    if event not in _BUILD_EVENTS or (
            duration_secs < _MIN_BUILD_ROW_S and event != _BACKEND_EVENT):
        return
    fields = {"event": event.rsplit("/", 1)[1],
              "program": kw.get("fun_name", "")}
    if event == _BACKEND_EVENT:
        # neither flag was raised where the cache was not asked
        fields["cache_hit"] = getattr(_building, "cache_hit", None)
        _building.cache_hit = None
    events.span_done("jit.program.build", "jit",
                     time.perf_counter_ns() - int(duration_secs * 1e9),
                     **fields)


def record_program_builds():
    """One ``jit.program.build`` row each time jax traces or lowers a
    program for a millisecond or more, and each time it compiles one or
    loads it from its cache, in this process
    (``event``, ``program``; ``cache_hit`` on the backend's row), from
    ``jax.monitoring`` listeners installed once, and only where the
    recorder is on. A listener learns of an interval at its end, so the
    rows are ``span_done``'s; it runs when a program is built, never on
    the call of one that is."""
    global _recording
    if _recording or not events.enabled():
        return
    import jax.monitoring as monitoring

    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_build_event)
    _recording = True


class _JaxPostImportHook(importlib.abc.MetaPathFinder):
    """Applies the platform config right after ``jax`` executes, and
    records the import and, from then on, the programs jax builds.

    The hook stays installed until ``exec_module`` actually runs (a bare
    ``find_spec('jax')`` probe from optional-dependency checks must not
    disarm it); it de-registers itself only once the config is applied.
    """

    def find_spec(self, name, path, target=None):
        if name != "jax":
            return None
        # Avoid re-entrancy during the nested lookup, then re-install so a
        # spec probe that never executes the module doesn't disarm us.
        try:
            sys.meta_path.remove(self)
        except ValueError:
            return None
        try:
            spec = importlib.util.find_spec("jax")
        finally:
            if "jax" not in sys.modules:
                sys.meta_path.insert(0, self)
        if spec is None or spec.loader is None:
            return spec
        orig_loader = spec.loader
        hook = self

        class _Loader(importlib.abc.Loader):
            def create_module(self, s):
                return orig_loader.create_module(s)

            def exec_module(self, mod):
                with events.span("jit.jax.import", "jit"):
                    orig_loader.exec_module(mod)
                platform = os.environ.get(ENV_VAR)
                if platform:
                    mod.config.update("jax_platforms", platform)
                record_program_builds()
                try:
                    sys.meta_path.remove(hook)
                except ValueError:
                    pass

        spec.loader = _Loader()
        return spec


def install_hook():
    """Place the compile cache, and arm the post-import hook where it has
    something to do once jax is there: a platform override to apply, or
    the recorder to tell of the programs jax builds."""
    _place_compile_cache()
    if not os.environ.get(ENV_VAR) and not events.enabled():
        return
    if "jax" in sys.modules:
        apply()
        record_program_builds()
        return
    if not any(isinstance(f, _JaxPostImportHook) for f in sys.meta_path):
        sys.meta_path.insert(0, _JaxPostImportHook())


def device_report() -> Optional[dict]:
    """The devices THIS process computes on, as jax reports them — or None
    where it has initialised no backend (asking would initialise one, and
    on a TPU host that takes the chip). What a replica's stats reply and a
    train worker's first report carry, so a caller can tell a chip run
    from a CPU run without touching jax itself."""
    if "jax" not in sys.modules:
        return None
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "pid": os.getpid(),
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }
