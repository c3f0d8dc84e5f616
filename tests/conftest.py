"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver's dryrun validates the same way).
This process is pinned with ``jax.config.update``; worker subprocesses get
the same via the ``RAY_TPU_JAX_PLATFORM`` post-import hook
(``ray_tpu._private.jax_platform``).

Mirrors the reference's in-process multi-node testing stance
(``python/ray/cluster_utils.py:135``): tests never need real clusters.
"""

import os

# Must be set before jax initializes a backend anywhere in the test tree.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["RAY_TPU_JAX_PLATFORM"] = "cpu"  # workers inherit this
# The suite compiles thousands of tiny CPU programs from dozens of
# processes at once: keep them out of the checkout's persistent compile
# cache (jax's own switch; tests of where the cache goes need it off too).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# Runtime race detection across the whole suite (the TSAN-config analog,
# ``.bazelrc:104-116``): loop/thread affinity assertions are live in every
# test process — an off-loop Connection write fails the test that did it.
os.environ.setdefault("RAY_TPU_THREAD_CHECKS", "1")
# Decoration-time static analysis across the whole suite (the offline
# `ray_tpu check` twin, ray_tpu/analysis/): every @ray_tpu.remote in any
# test is linted as it registers. Warnings only — registration must never
# hard-fail (tests/test_static_analysis.py asserts exactly that).
os.environ.setdefault("RAY_TPU_STATIC_CHECKS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_sessionstart(session):
    """Stale-zygote pre-flight: worker/agent processes reparented to
    init (ppid==1) survive hard-killed bench/test runs and trip the
    chaos suite's HOST-WIDE orphaned-process invariant — PR 9 burned a
    full tier-1 triage on 16 phantom reds from exactly this. Warn up
    front with the kill command (never pkill by pattern — see
    session-traps); the chaos-marked tests fail fast on it below."""
    try:
        from ray_tpu.util.invariants import orphaned_session_procs

        orphans = orphaned_session_procs()
    except Exception:
        return
    msgs = []
    if orphans:
        pids = " ".join(str(p["pid"]) for p in orphans)
        msgs.append(
            f"PRE-FLIGHT: {len(orphans)} stale ppid==1 session "
            f"zygote(s) from an earlier hard-killed run are live on "
            f"this host — chaos/invariants tests WILL red out. "
            f"Clean first: kill -9 {pids}")
    try:
        import glob

        arenas = glob.glob("/dev/shm/rtpu_*")
    except OSError:
        arenas = []
    if len(arenas) > 64:
        # Hard-killed sessions leak their arenas; past ~512 of them new
        # arena creation starts failing host-wide with misleading
        # "no holder could serve" pull errors (r10 burned a bench triage
        # on exactly this). Live sessions hold theirs open, so cleanup
        # is only safe when nothing is running.
        msgs.append(
            f"PRE-FLIGHT: {len(arenas)} stale /dev/shm/rtpu_* arenas "
            f"from earlier hard-killed runs — past ~512 the store "
            f"fails host-wide. With NO live ray_tpu processes, clean "
            f"via: rm -f /dev/shm/rtpu_*")
    if msgs:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        for msg in msgs:
            if tr is not None:
                tr.write_line(msg, yellow=True, bold=True)
            else:  # pragma: no cover - no terminal plugin (unusual)
                print(msg)


@pytest.fixture(autouse=True)
def _zygote_preflight(request):
    """Chaos-marked tests assert host-wide end-state invariants; stale
    pre-existing zygotes make every one of them a false red. Fail FAST
    with the exact remediation instead of 300s of misleading failures.
    A short settle window first: a zygote from the PREVIOUS test's
    just-torn-down cluster reparents to init for a few seconds on its
    way out — only a PERSISTENT orphan is pollution (the first full-
    suite run of this fixture false-red one chaos test on exactly that
    transient)."""
    if request.node.get_closest_marker("chaos") is not None:
        import time

        from ray_tpu.util.invariants import orphaned_session_procs

        deadline = time.time() + 8.0
        orphans = orphaned_session_procs()
        while orphans and time.time() < deadline:
            time.sleep(0.5)
            orphans = orphaned_session_procs()
        if orphans:
            pids = " ".join(str(p["pid"]) for p in orphans)
            pytest.fail(
                f"HOST POLLUTION (pre-existing, not this test): "
                f"{len(orphans)} stale ppid==1 session zygote(s) "
                f"persisted >8s — they would trip the chaos orphan "
                f"invariant host-wide. Kill them by pid first: "
                f"kill -9 {pids}", pytrace=False)
    yield


@pytest.fixture(scope="module")
def ray_cluster():
    """A started ray_tpu cluster shared by a test module."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, probe_tpu=False, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _end_invariants(request):
    """Opt-in end-of-test invariant check (``@pytest.mark.invariants``):
    after the test body, assert the cluster drained clean (GCS lanes
    empty, tenant usage zero, no wedged workers), shut it down, and
    assert the HOST is clean too (no orphaned session processes, shm
    arena unlinked). The chaos suite (benchmarks/chaos_suite.py) runs
    the same ``ray_tpu.util.invariants`` core — one definition of
    "recovered"."""
    yield
    if request.node.get_closest_marker("invariants") is None:
        return
    import ray_tpu
    from ray_tpu.util import invariants

    session = None
    if ray_tpu.is_initialized():
        from ray_tpu._private.worker import global_worker

        session = global_worker().session_name
        invariants.check_cluster_invariants()
        ray_tpu.shutdown()
    invariants.check_host_invariants(session)


@pytest.fixture(scope="session")
def cpu_mesh8():
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must provide 8 virtual CPU devices"
    return devices[:8]


@pytest.fixture
def slow_device(monkeypatch):
    """No step has ended when the engine asks: as on the chip, where a
    step takes longer than the host's part of a call (the CPU ends a toy
    step before the call returns, and nothing would stay in flight)."""
    from ray_tpu.models import paged

    monkeypatch.setattr(paged._Flight, "ended", lambda self: False)


@pytest.fixture
def prompt_device(monkeypatch):
    """Every step has ended when the engine asks: each call fetches the
    step it dispatched, so a ``serve.engine.step`` row is one step's (the
    CPU ends most toy steps in time, not all)."""
    from ray_tpu.models import paged

    monkeypatch.setattr(paged._Flight, "ended", lambda self: True)


@pytest.fixture
def streams():
    """-> f(engine, {rid: (prompt, max_new)}, **how): what each request
    streams, and the most steps the engine ever left in flight when
    ``step()`` returned."""
    def drive(eng, reqs, **how):
        for r, (prompt, n) in reqs.items():
            eng.submit(r, prompt, max_new_tokens=n, **how)
        got, deepest = {r: [] for r in reqs}, 0
        while eng.has_work():
            for rid, tok in eng.step():
                if tok is not None:
                    got[rid].append(tok)
            deepest = max(deepest, len(eng._flights))
        return got, deepest
    return drive
