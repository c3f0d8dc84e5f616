"""Accelerator manager tests (TPU + GPU/Neuron plugin breadth).

Reference model: ``python/ray/tests/accelerators/`` — managers detect
counts/types via faked tool output, pin via env vars.
"""

import pytest

from ray_tpu.accelerators import (GPUAcceleratorManager,
                                  NeuronAcceleratorManager,
                                  detect_accelerator_resources,
                                  get_accelerator_manager)


def test_gpu_manager_with_fake_smi():
    def fake(argv):
        assert argv[0].endswith("nvidia-smi")
        assert argv[1] == "--query-gpu=index,name"  # ONE combined probe
        return ("0, NVIDIA H100 80GB HBM3\n"
                "1, NVIDIA H100 80GB HBM3\n")

    m = GPUAcceleratorManager(exec_fn=fake)
    assert m.get_current_node_num_accelerators() == 2
    assert m.get_current_node_accelerator_type() == "H100"
    assert m.get_current_node_extra_resources() == {
        "accelerator_type:H100": 1.0}
    env = {}
    m.set_visible_accelerators(env, ["0"])
    assert env == {"CUDA_VISIBLE_DEVICES": "0"}


def test_gpu_manager_gated_without_smi():
    m = GPUAcceleratorManager()  # no nvidia-smi on this host
    assert m.get_current_node_num_accelerators() == 0
    assert m.get_current_node_accelerator_type() is None


def test_neuron_manager_with_fake_ls():
    import json

    def fake(argv):
        return json.dumps([{"nc_count": 2}, {"nc_count": 2}])

    m = NeuronAcceleratorManager(exec_fn=fake)
    assert m.get_current_node_num_accelerators() == 4
    assert m.get_current_node_accelerator_type() == "aws-neuron"
    env = {}
    m.set_visible_accelerators(env, ["0", "1"])
    assert env == {"NEURON_RT_VISIBLE_CORES": "0,1"}


def test_registry_and_detection():
    assert get_accelerator_manager("GPU") is not None
    assert get_accelerator_manager("TPU") is not None
    res = detect_accelerator_resources()  # no GPUs/TPUs here: no crash
    assert isinstance(res, dict)


# ------------------------------------------------- one owner for the chip

def test_worker_without_tpu_grant_is_pinned_and_granted_one_is_not():
    """The scheduler decides who owns the chip: work that holds a TPU
    grant draws from the ``tpu`` worker pool, and the node agent pins every
    other worker's jax to the CPU in the environment it spawns it with."""
    from ray_tpu._private.node import worker_spawn_env
    from ray_tpu.accelerators.tpu import (TPU_POOL, holds_tpu_grant,
                                          worker_pool_key)

    assert worker_pool_key("", {"CPU": 1.0}) == ""
    assert worker_pool_key("", {"CPU": 1.0, "TPU": 0.0}) == ""
    assert worker_pool_key("", {"TPU": 1.0}) == TPU_POOL
    assert worker_pool_key("venv123", {"TPU": 4.0}) == TPU_POOL + "+venv123"
    assert worker_pool_key("venv123", None) == "venv123"

    plain = worker_spawn_env("", "ab" * 8)
    assert plain["RAY_TPU_JAX_PLATFORM"] == "cpu"
    assert plain["TPU_VISIBLE_CHIPS"] == ""
    assert "RAY_TPU_ENV_KEY" not in plain
    venv = worker_spawn_env("venv123", "ab" * 8)
    assert venv["RAY_TPU_JAX_PLATFORM"] == "cpu"
    for key in (TPU_POOL, TPU_POOL + "+venv123"):
        assert holds_tpu_grant(key)
        granted = worker_spawn_env(key, "ab" * 8)
        assert granted == {"RAY_TPU_NODE_ID": "ab" * 8,
                           "RAY_TPU_ENV_KEY": key}
    assert not holds_tpu_grant("tpuish") and not holds_tpu_grant("")


def test_gcs_records_draw_tpu_work_from_the_tpu_pool():
    from ray_tpu._private.gcs import ActorRecord, LeaseDemand, TaskRecord
    from ray_tpu._private.ids import ActorID, TaskID

    tpu, cpu = {"res": {"TPU": 1.0}}, {"res": {"CPU": 1.0}}
    assert TaskRecord(TaskID.from_random(), {"opts": tpu}, None
                      ).env_key == "tpu"
    assert TaskRecord(TaskID.from_random(), {"opts": cpu}, None
                      ).env_key == ""
    assert ActorRecord(ActorID.from_random(), {"opts": tpu}, None
                       ).env_key == "tpu"
    assert LeaseDemand(None, {"key": "k", **tpu}).env_key == "tpu"
    assert LeaseDemand(None, {"key": "k", **cpu}).env_key == ""


def test_spawned_workers_are_pinned_by_grant(ray_cluster_tpu):
    """End to end on the CPU: the environment a real worker process sees,
    for a zygote-forked worker of each pool."""
    import os

    import ray_tpu

    @ray_tpu.remote
    def seen():
        return {k: os.environ.get(k) for k in (
            "RAY_TPU_ENV_KEY", "RAY_TPU_JAX_PLATFORM", "TPU_VISIBLE_CHIPS",
            "JAX_COMPILATION_CACHE_DIR")}, os.getpid()

    plain, _ = ray_tpu.get(seen.remote())
    assert plain["RAY_TPU_ENV_KEY"] is None
    assert plain["RAY_TPU_JAX_PLATFORM"] == "cpu"
    assert plain["TPU_VISIBLE_CHIPS"] == ""
    granted, pid1 = ray_tpu.get(seen.options(num_tpus=1).remote())
    assert granted["RAY_TPU_ENV_KEY"] == "tpu"
    assert granted["TPU_VISIBLE_CHIPS"] is None
    # a task that held the grant may have taken the chip, and a process
    # keeps it until it exits: the next grant gets a fresh worker
    _, pid2 = ray_tpu.get(seen.options(num_tpus=1).remote())
    assert pid1 != pid2
    # driver and forked workers agree on where compiled programs go
    from ray_tpu._private.jax_platform import compile_cache_dir

    assert plain["JAX_COMPILATION_CACHE_DIR"] == compile_cache_dir()
    assert granted["JAX_COMPILATION_CACHE_DIR"] == compile_cache_dir()



@pytest.fixture
def ray_cluster_tpu(monkeypatch):
    import ray_tpu

    # the suite-wide pin must not be what pins the plain worker
    monkeypatch.delenv("RAY_TPU_JAX_PLATFORM")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ray_tpu.init(num_cpus=2, num_tpus=1, probe_tpu=False)
    yield
    ray_tpu.shutdown()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_resolution(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR where set, else the fixed in-checkout
    path — the same answer in a driver and in a forked worker, because
    install_hook exports it before anything is spawned."""
    import os
    import subprocess
    import sys

    import ray_tpu
    from ray_tpu._private import jax_platform

    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(ray_tpu.__file__)))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(checkout, ".jax_cache")
    assert jax_platform.compile_cache_dir() == want
    # a fresh process (driver, head, zygote alike) exports it on import,
    # so every child inherits the same directory
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, ray_tpu; "
         "print(os.environ['JAX_COMPILATION_CACHE_DIR']); "
         "print(os.environ['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'])"],
        capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONPATH": checkout}).stdout.split()
    assert out == [want, "0"]


def test_build_llm_app_asks_for_a_chip_where_the_cluster_has_one(
        ray_cluster_tpu):
    from ray_tpu.serve.llm import build_llm_app

    app = build_llm_app(lambda: None)
    assert app.deployment.ray_actor_options == {"num_tpus": 1}


def test_tpu_train_workers_cannot_share_a_host(ray_cluster_tpu):
    """Two chip-holding processes on one host would fight over the chips
    inside jax.distributed.initialize: refused at formation, in words."""
    from ray_tpu.train.worker_group import (WorkerGroup,
                                            WorkerGroupFormationError)

    with pytest.raises(WorkerGroupFormationError,
                       match="one chip-holding process per host"):
        WorkerGroup(2, {"CPU": 0.5, "TPU": 0.5}, formation_timeout_s=30)


# ----------------------------------------- nothing hides the device: bench

def test_bench_peak_table_is_keyed_by_device_kind_and_raises_on_unknown():
    import types

    import bench

    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench.detect_peak_flops(v5e) == 197e12
    with pytest.raises(ValueError, match="TPU v9 mega"):
        bench.detect_peak_flops(types.SimpleNamespace(
            device_kind="TPU v9 mega", platform="tpu"))
    with pytest.raises(ValueError):
        bench.detect_peak_flops(types.SimpleNamespace(
            device_kind="cpu", platform="cpu"))


def test_bench_train_mode_needs_a_chip():
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "--mode", "train"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""   # no number under a per-chip name
