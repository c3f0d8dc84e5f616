"""runtime_env subsystem + accelerator manager tests.

Covers the reference's runtime-env behaviors (env_vars isolation,
working_dir shipping, py_modules imports — ``python/ray/_private/
runtime_env/``) and the TPU accelerator manager's topology math
(``_private/accelerators/tpu.py:71``).
"""

import os
import sys

import pytest

import ray_tpu
from ray_tpu.runtime_env import (RuntimeEnvContext, RuntimeEnvPlugin,
                                 package_directory, ensure_local_package,
                                 register_plugin, unregister_plugin,
                                 setup_runtime_env, validate_runtime_env)


# ------------------------------------------------------------ unit: packaging


def test_package_directory_deterministic(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "a.txt").write_text("hello")
    (d / "sub").mkdir()
    (d / "sub" / "b.py").write_text("X = 1")
    uri1, data1 = package_directory(str(d))
    uri2, data2 = package_directory(str(d))
    assert uri1 == uri2 and data1 == data2
    assert uri1.startswith("pkg://")
    (d / "a.txt").write_text("changed")
    uri3, _ = package_directory(str(d))
    assert uri3 != uri1


def test_package_excludes_pycache(tmp_path):
    d = tmp_path / "pkg"
    (d / "__pycache__").mkdir(parents=True)
    (d / "__pycache__" / "junk.pyc").write_text("x")
    (d / "keep.py").write_text("Y = 2")
    _, data = package_directory(str(d))
    import io
    import zipfile

    names = zipfile.ZipFile(io.BytesIO(data)).namelist()
    assert names == ["keep.py"]


def test_ensure_local_package_caches(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "f.txt").write_text("data")
    uri, data = package_directory(str(d))
    calls = []

    def fetch(u):
        calls.append(u)
        return data

    cache = str(tmp_path / "cache")
    p1 = ensure_local_package(uri, fetch, cache_dir=cache)
    p2 = ensure_local_package(uri, fetch, cache_dir=cache)
    assert p1 == p2 and len(calls) == 1
    assert open(os.path.join(p1, "f.txt")).read() == "data"


def test_validate_rejects_unknown_and_conda():
    with pytest.raises(ValueError, match="unknown runtime_env"):
        validate_runtime_env({"nonsense_key": 1})
    with pytest.raises(ValueError, match="conda"):
        validate_runtime_env({"conda": "myenv"})


def test_pip_env_routing_guard(monkeypatch):
    """pip envs are satisfied at worker spawn (venv workers); the worker-
    side plugin only checks the scheduler routed the task to a worker of
    the right env pool (full isolation covered by test_runtime_env_pip)."""
    from ray_tpu.runtime_env.pip_env import env_key, normalize_spec

    spec = normalize_spec(["numpy"], "pip")
    monkeypatch.setenv("RAY_TPU_ENV_KEY", env_key(spec))
    ctx = setup_runtime_env({"pip": ["numpy"]}, fetch=lambda u: None,
                            apply=False)
    assert isinstance(ctx, RuntimeEnvContext)
    monkeypatch.setenv("RAY_TPU_ENV_KEY", "somethingelse")
    with pytest.raises(RuntimeError, match="env-pool routing"):
        setup_runtime_env({"pip": ["numpy"]}, fetch=lambda u: None,
                          apply=False)


def test_custom_plugin_roundtrip():
    class MarkerPlugin(RuntimeEnvPlugin):
        name = "marker"

        def create(self, value, ctx, fetch):
            ctx.env_vars["MARKER_VALUE"] = str(value)

    register_plugin(MarkerPlugin())
    try:
        ctx = setup_runtime_env({"marker": 42}, fetch=lambda u: None,
                                apply=False)
        assert ctx.env_vars["MARKER_VALUE"] == "42"
    finally:
        unregister_plugin("marker")


# ------------------------------------------------------- cluster integration


def test_env_vars_per_task(ray_cluster):
    @ray_tpu.remote
    def read_env():
        return os.environ.get("MY_RENV_VAR")

    ref = read_env.options(
        runtime_env={"env_vars": {"MY_RENV_VAR": "abc"}}).remote()
    assert ray_tpu.get(ref) == "abc"
    # A later plain task must not see the mutation (dedicated worker died).
    assert ray_tpu.get(read_env.remote()) is None


def test_working_dir_ships_files(ray_cluster, tmp_path):
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "config.txt").write_text("payload-123")
    (proj / "helper.py").write_text("VALUE = 'from-helper'\n")

    @ray_tpu.remote
    def use_working_dir():
        import helper  # shipped module, importable from cwd

        with open("config.txt") as f:
            return f.read(), helper.VALUE

    ref = use_working_dir.options(
        runtime_env={"working_dir": str(proj)}).remote()
    content, helper_val = ray_tpu.get(ref)
    assert content == "payload-123"
    assert helper_val == "from-helper"


def test_py_modules_package_import(ray_cluster, tmp_path):
    pkg = tmp_path / "shipped_pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("NAME = 'shipped'\n")
    (pkg / "mod.py").write_text("def f():\n    return 99\n")

    @ray_tpu.remote
    def use_module():
        import shipped_pkg
        from shipped_pkg import mod

        return shipped_pkg.NAME, mod.f()

    ref = use_module.options(
        runtime_env={"py_modules": [str(pkg)]}).remote()
    assert ray_tpu.get(ref) == ("shipped", 99)


def test_actor_runtime_env(ray_cluster):
    @ray_tpu.remote
    class EnvActor:
        def get(self, k):
            return os.environ.get(k)

    a = EnvActor.options(
        runtime_env={"env_vars": {"ACTOR_RENV": "yes"}}).remote()
    assert ray_tpu.get(a.get.remote("ACTOR_RENV")) == "yes"


# ------------------------------------------------------------- accelerators


def test_tpu_manager_topology(monkeypatch):
    from ray_tpu.accelerators import TPUAcceleratorManager

    mgr = TPUAcceleratorManager()
    for var in ("TPU_ACCELERATOR_TYPE", "TPU_WORKER_ID",
                "TPU_WORKER_HOSTNAMES", "TPU_CHIPS_PER_HOST_BOUNDS",
                "RAY_TPU_CHIPS"):
        monkeypatch.delenv(var, raising=False)

    assert mgr.get_current_node_num_accelerators() == 0

    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5p-128")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    # v5p-128: 128 cores / 2 cores-per-chip = 64 chips, 4 per host = 16 hosts
    assert mgr.get_pod_num_chips("v5p-128") == 64
    assert mgr.get_current_node_num_accelerators() == 4
    assert mgr.get_current_pod_worker_count() == 16
    extra = mgr.get_current_node_extra_resources()
    assert extra["TPU-v5p-128-head"] == 1.0
    assert extra["TPU-v5p-128"] == 4.0

    monkeypatch.setenv("TPU_WORKER_ID", "3")
    assert "TPU-v5p-128-head" not in mgr.get_current_node_extra_resources()

    # Single-host v6e-8: 8 cores = 8 chips on one host
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v6e-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    assert mgr.get_pod_num_chips("v6e-8") == 8
    assert mgr.get_current_node_num_accelerators() == 8
    assert mgr.get_current_pod_worker_count() == 1


def test_tpu_visible_chip_pinning():
    from ray_tpu.accelerators import get_accelerator_manager

    mgr = get_accelerator_manager("TPU")
    env = {}
    mgr.set_visible_accelerators(env, ["0", "1"])
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    env = {}
    mgr.set_visible_accelerators(env, [])
    assert env["RAY_TPU_JAX_PLATFORM"] == "cpu"


def test_detect_node_resources_includes_tpu(monkeypatch):
    from ray_tpu._private.node import detect_node_resources

    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS", raising=False)
    # Topology env alone must NOT register chips (dev hosts export stale
    # topology); an explicit count signal is required.
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    res = detect_node_resources(num_cpus=2)
    assert "TPU" not in res
    monkeypatch.setenv("RAY_TPU_CHIPS", "8")
    res = detect_node_resources(num_cpus=2)
    assert res["TPU"] == 8.0
    assert res["TPU-v5e-16"] == 8.0
    assert res["TPU-v5e-16-head"] == 1.0
