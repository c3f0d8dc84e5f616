"""Object store tests (model: reference ``test_basic_2.py`` / plasma tests)."""

import numpy as np
import pytest


def test_put_get_roundtrip(ray_cluster):
    ray_tpu = ray_cluster
    for value in [1, "s", [1, 2], {"a": (1, 2)}, None, b"bytes", 3.14]:
        assert ray_tpu.get(ray_tpu.put(value)) == value


def test_put_get_numpy_zero_copy(ray_cluster):
    ray_tpu = ray_cluster
    arr = np.random.rand(1024, 256).astype(np.float32)
    out = ray_tpu.get(ray_tpu.put(arr))
    np.testing.assert_array_equal(arr, out)
    # Large arrays come back as views over shared memory (zero-copy).
    assert not out.flags["OWNDATA"]


def test_put_of_ref_rejected(ray_cluster):
    ray_tpu = ray_cluster
    with pytest.raises(TypeError):
        ray_tpu.put(ray_tpu.put(1))


def test_ref_passed_through_task(ray_cluster):
    ray_tpu = ray_cluster
    ref = ray_tpu.put(np.arange(100_000))

    @ray_tpu.remote
    def total(r):
        return int(r.sum())

    assert ray_tpu.get(total.remote(ref)) == sum(range(100_000))


def test_ref_forwarded_between_tasks(ray_cluster):
    ray_tpu = ray_cluster

    @ray_tpu.remote
    def make():
        import numpy as _np

        return _np.ones(200_000)

    @ray_tpu.remote
    def use(container):
        import ray_tpu as rt

        return float(rt.get(container["r"]).sum())

    r = make.remote()
    assert ray_tpu.get(use.remote({"r": r})) == 200_000.0


def test_get_list(ray_cluster):
    ray_tpu = ray_cluster
    refs = [ray_tpu.put(i) for i in range(10)]
    assert ray_tpu.get(refs) == list(range(10))


def test_wait_all(ray_cluster):
    ray_tpu = ray_cluster
    refs = [ray_tpu.put(i) for i in range(5)]
    ready, not_ready = ray_tpu.wait(refs, num_returns=5, timeout=5)
    assert len(ready) == 5 and not not_ready


def test_shared_get_same_object(ray_cluster):
    """Two tasks getting the same large ref both see the data."""
    ray_tpu = ray_cluster
    arr = np.random.rand(300_000)
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def check(r, expected_sum):
        return abs(float(r.sum()) - expected_sum) < 1e-6

    s = float(arr.sum())
    assert all(ray_tpu.get([check.remote(ref, s) for _ in range(4)]))


def test_large_args_released_after_task(ray_cluster):
    """Shm-resident argument bundles (>INLINE_THRESHOLD) must drop to
    refcount 0 once the consuming call completes — the round-3 arg path
    leaked one arena block per large-arg call for the driver's lifetime
    (reference semantics: DependencyResolver releases inlined deps after
    dispatch, ``transport/dependency_resolver.h``)."""
    import time

    ray_tpu = ray_cluster
    from ray_tpu.util.state import list_objects

    @ray_tpu.remote
    class A:
        def nbytes(self, arr):
            return arr.nbytes

    a = A.remote()
    arr = np.zeros(300 * 1024, dtype=np.uint8)
    assert ray_tpu.get([a.nbytes.remote(arr) for _ in range(12)]) \
        == [arr.nbytes] * 12

    @ray_tpu.remote
    def task_nbytes(arr):
        return arr.nbytes

    assert ray_tpu.get([task_nbytes.remote(arr) for _ in range(12)]) \
        == [arr.nbytes] * 12

    # Release deltas batch on a 100ms flusher; give the GCS a few cycles.
    deadline = time.time() + 5
    while time.time() < deadline:
        pinned = [o for o in list_objects()
                  if o["refcount"] > 0 and o["nbytes"] >= 300 * 1024]
        if not pinned:
            break
        time.sleep(0.2)
    assert not pinned, f"leaked arg bundles: {pinned[:4]}"


def test_fire_and_forget_large_arg_released(ray_cluster):
    """Refs dropped BEFORE completion (fire-and-forget with retryable
    tasks) must not strand a lineage spec pinning the arg bundle."""
    import time

    ray_tpu = ray_cluster
    from ray_tpu.util.state import list_objects

    @ray_tpu.remote(retries=3)
    def produce(arr):
        return arr * 2  # >INLINE_THRESHOLD shm result

    arr = np.zeros(300 * 1024, dtype=np.uint8)
    for _ in range(6):
        # Dropping the ref IS the test subject: the store must drain
        # refs abandoned before completion.  # raylint: disable=RTL007
        produce.remote(arr)  # raylint: disable=RTL007

    deadline = time.time() + 8
    while time.time() < deadline:
        pinned = [o for o in list_objects()
                  if o["refcount"] > 0 and o["nbytes"] >= 300 * 1024]
        if not pinned:
            break
        time.sleep(0.25)
    assert not pinned, f"stranded specs/args: {pinned[:4]}"


def test_actor_ctor_args_released_on_death(ray_cluster):
    """Large ctor arg bundles stay pinned while the actor can restart,
    and release on permanent death."""
    import time

    ray_tpu = ray_cluster
    from ray_tpu.util.state import list_objects

    @ray_tpu.remote
    class Big:
        def __init__(self, arr):
            self.n = arr.nbytes

        def n_bytes(self):
            return self.n

    arr = np.zeros(400 * 1024, dtype=np.uint8)
    a = Big.remote(arr)
    assert ray_tpu.get(a.n_bytes.remote()) == arr.nbytes
    del arr

    # Alive actor: the ctor bundle must still be resolvable (pinned).
    time.sleep(0.4)
    assert any(o["refcount"] > 0 and o["nbytes"] >= 400 * 1024
               for o in list_objects())

    ray_tpu.kill(a)
    deadline = time.time() + 8
    while time.time() < deadline:
        pinned = [o for o in list_objects()
                  if o["refcount"] > 0 and o["nbytes"] >= 400 * 1024]
        if not pinned:
            break
        time.sleep(0.25)
    assert not pinned, f"ctor arg bundle leaked past actor death: {pinned}"


def test_native_store_failure_is_an_error_not_a_quiet_switch(monkeypatch):
    """A host that cannot build native/shm_store.cc says so; the Python
    store is something one asks for by name."""
    from ray_tpu._private import object_store, shm_native

    def no_compiler():
        raise RuntimeError("building shm_store.cc failed: g++ not found")

    monkeypatch.setattr(shm_native, "_lib", None)
    monkeypatch.setattr(shm_native, "_build_lib", no_compiler)
    with pytest.raises(RuntimeError, match="g\\+\\+ not found"):
        object_store.make_store("session_nobuild")
    monkeypatch.setenv("RAY_TPU_DISABLE_NATIVE_STORE", "1")
    store = object_store.make_store("session_nobuild")
    assert type(store).__name__ == "PyShmStore"
