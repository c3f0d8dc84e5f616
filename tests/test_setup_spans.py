"""The set-up path's spans: one CPU session (``init`` with a declared chip ->
``serve.run`` of a toy ``LLMServer`` that asks for it -> ``JaxTrainer.fit``
with one chip-holding worker -> ``shutdown``), every process's rows read back
from the session's spill directory; the compile listener on its own; the
node's probe against a stand-in child; and the recorder off."""

import asyncio
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.util import events

SPANS = {
    # name: (who writes it, fields it must carry)
    "gcs.cluster.start": ("driver", {"started_head"}),
    "gcs.head.spawn": ("driver", set()),
    "gcs.driver.connect": ("driver", set()),
    "lease.actor.place": ("head", {"actor", "resources", "node",
                                   "worker_pid"}),
    "lease.worker.spawn": ("head", {"worker_pid", "pool", "zygote"}),
    "lease.worker.boot": ("worker", {"worker_pid", "pool"}),
    "lease.actor.load": ("worker", {"actor", "worker_pid"}),
    "serve.app.run": ("driver", {"app"}),
    "serve.replica.init": ("worker", {"actor", "worker_pid", "deployment"}),
    "serve.replica.weights": ("worker", set()),
    "serve.replica.engine": ("worker", {"slots", "pages"}),
    "train.fit.start": ("driver", {"run", "workers"}),
    "train.worker.setup": ("worker", {"actor", "worker_pid", "rank",
                                      "world_size"}),
    "jit.jax.import": ("worker", set()),
    "jit.program.build": ("worker", {"event", "program"}),
}


def _toy_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=96, d_model=64, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu import train

    train.report({"y": float(jax.jit(lambda x: x.sum())(jnp.ones(4)))})


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """-> (span rows of every process, the driver's pid)."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    events.reset()
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        session_dir = ray_tpu._private.worker.global_worker().session_dir
        app = serve.deployment(
            LLMServer, ray_actor_options={"num_tpus": 1}).bind(
            _toy_model, max_slots=2, max_len=64, page_size=8, num_pages=24)
        handle = serve.run(app, name="setup-spans", route_prefix=None)
        out = handle.remote({"prompt": [1, 2, 3],
                             "max_new_tokens": 2}).result(timeout=120)
        assert len(out["tokens"]) == 2
        serve.shutdown()
        result = JaxTrainer(
            _loop, scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=1),
            run_config=RunConfig(
                name="setup-spans",
                storage_path=str(tmp_path_factory.mktemp("train")))).fit()
        assert result.error is None, result.error
    finally:
        ray_tpu.shutdown()
    rows = [r for r in events.read_spill(pid=None, session_dir=session_dir)
            if "dur_ns" in r["fields"]]
    return rows, os.getpid()


def _named(rows, name):
    return [r for r in rows if r["name"] == name]


def _end(row):
    return row["fields"]["t0_ns"] + row["fields"]["dur_ns"]


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_written_with_its_fields(session, name):
    rows, driver = session
    who, fields = SPANS[name]
    found = _named(rows, name)
    assert found, f"no {name} row in any process's spill file"
    for r in found:
        assert fields <= set(r["fields"]), (name, r["fields"])
        assert r["plane"] == name.split(".")[0] in events.PLANES
        assert r["fields"]["dur_ns"] >= 0 and r["fields"]["sid"] > 0
        assert (r["pid"] == driver) == (who == "driver"), (name, r["pid"])
    if who == "driver":
        assert len(found) == 1     # one init, one serve.run, one attempt


def test_four_kinds_of_process_wrote_a_file(session):
    """The driver, the head (GCS and node agent are one process on the head
    node) and the two chip-holding workers each left rows."""
    rows, driver = session
    head = {r["pid"] for r in _named(rows, "lease.actor.place")}
    assert head == {r["pid"] for r in _named(rows, "lease.worker.spawn")}
    workers = {r["pid"] for r in _named(rows, "lease.worker.boot")}
    assert len(head) == 1 and driver not in head | workers
    assert len({r["pid"] for r in _named(rows, "serve.replica.init")}
               | {r["pid"] for r in _named(rows, "train.worker.setup")}) == 2
    assert not head & workers


@pytest.mark.parametrize("ctor", ["serve.replica.init", "train.worker.setup"])
def test_actor_joins_placement_spawn_boot_and_constructor(session, ctor):
    rows, _ = session
    (init,) = _named(rows, ctor)
    actor, pid = init["fields"]["actor"], init["fields"]["worker_pid"]
    assert pid == init["pid"]
    (place,) = [r for r in _named(rows, "lease.actor.place")
                if r["fields"]["actor"] == actor]
    (boot,) = [r for r in _named(rows, "lease.worker.boot")
               if r["fields"]["worker_pid"] == pid]
    (load,) = [r for r in _named(rows, "lease.actor.load")
               if r["fields"]["actor"] == actor]
    (spawn,) = [r for r in _named(rows, "lease.worker.spawn")
                if r["fields"]["worker_pid"] == pid]
    assert place["fields"]["worker_pid"] == load["fields"]["worker_pid"] == pid
    assert boot["pid"] == load["pid"] == pid
    assert place["fields"]["resources"]["TPU"] == 1.0
    assert spawn["fields"]["pool"] == boot["fields"]["pool"] == "tpu"
    # one clock across the four processes
    (start,) = _named(rows, "gcs.cluster.start")
    assert start["fields"]["t0_ns"] == min(r["fields"]["t0_ns"] for r in rows)
    # the agent forks, the worker boots and says hello, the GCS grants,
    # the worker loads the class, the constructor runs
    assert _end(spawn) <= _end(boot) <= _end(place) + 50e6
    assert _end(place) <= load["fields"]["t0_ns"] + 50e6
    assert _end(load) <= init["fields"]["t0_ns"] <= _end(load) + 50e6
    # a boot begins with its process, before any code of ours ran: at
    # the fork, inside the agent's spawn span (the kernel's 10 ms ticks)
    assert spawn["fields"]["t0_ns"] - 20e6 <= boot["fields"]["t0_ns"] \
        <= _end(spawn) + 20e6


def test_constructor_children_nest_in_the_replica_span(session):
    rows, _ = session
    (init,) = _named(rows, "serve.replica.init")
    for name in ("serve.replica.weights", "serve.replica.engine"):
        (child,) = _named(rows, name)
        assert child["pid"] == init["pid"]
        assert child["fields"]["parent"] == init["fields"]["sid"]
        assert init["fields"]["t0_ns"] <= child["fields"]["t0_ns"]
        assert _end(child) <= _end(init)


def test_entry_spans_cover_their_actor_start(session):
    rows, _ = session
    for entry, ctor in (("serve.app.run", "serve.replica.init"),
                        ("train.fit.start", "train.worker.setup")):
        (e,), (c,) = _named(rows, entry), _named(rows, ctor)
        assert e["fields"]["t0_ns"] < c["fields"]["t0_ns"] < _end(e)


def test_build_rows_name_program_event_and_cache(session):
    rows, _ = session
    (init,) = _named(rows, "serve.replica.init")
    built = [r for r in _named(rows, "jit.program.build")
             if r["pid"] == init["pid"]]
    kinds = {r["fields"]["event"] for r in built}
    assert kinds == {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                     "backend_compile_duration"}
    for r in built:
        assert ("cache_hit" in r["fields"]) == (
            r["fields"]["event"] == "backend_compile_duration")
    assert any("_paged_step" in r["fields"]["program"] for r in built)


# ------------------------------------------------- the compile listener
@pytest.fixture
def ring():
    events.reset()
    yield
    events._enabled = True
    events.reset()


def _build_rows():
    rows, _ = events.drain()
    return [events.row_to_dict(r) for r in rows
            if r[1] == "jit.program.build"]


def test_first_call_writes_one_group_and_the_second_none(ring, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import jax_platform

    jax_platform.record_program_builds()    # jax is imported: idempotent
    monkeypatch.setattr(jax_platform, "_MIN_BUILD_ROW_S", 0.0)

    def a_program_of_this_test(x):
        return x * 3 + 1

    f = jax.jit(a_program_of_this_test)
    x = jnp.ones(5)
    _build_rows()
    t0 = time.perf_counter_ns()
    f(x).block_until_ready()
    t1 = time.perf_counter_ns()
    mine = [r for r in _build_rows()
            if "a_program_of_this_test" in r["fields"]["program"]]
    assert [r["fields"]["event"] for r in mine] == [
        "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
        "backend_compile_duration"]
    for r in mine:          # backdated from the listener's call: inside
        assert t0 - 5e6 <= r["fields"]["t0_ns"] and _end(r) <= t1
    assert mine[2]["fields"]["cache_hit"] in (None, False, True)
    f(x).block_until_ready()
    assert not _build_rows()


def test_backend_rows_count_what_the_benchmarks_counter_counts(ring):
    import jax
    import jax.numpy as jnp

    from perfbench.program import CompileCounter

    counter = CompileCounter()
    _build_rows()
    for k in range(3):
        jax.jit(lambda x, k=k: x * k - 2)(jnp.ones(k + 2))
    backend = [r for r in _build_rows()
               if r["fields"]["event"] == "backend_compile_duration"]
    assert len(backend) == counter.count >= 3


def test_a_short_trace_writes_no_row_but_its_backend_event_does(
        ring, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import jax_platform

    monkeypatch.setattr(jax_platform, "_MIN_BUILD_ROW_S", 3600.0)
    _build_rows()
    jax.jit(lambda x: x / 9)(jnp.ones(7))
    assert {r["fields"]["event"] for r in _build_rows()} == {
        "backend_compile_duration"}


def test_listener_with_the_recorder_off_writes_nothing(ring):
    import jax
    import jax.numpy as jnp

    events._enabled = False
    jax.jit(lambda x: x - 7)(jnp.ones(6))
    events._enabled = True
    assert not _build_rows()


def test_no_listener_is_installed_with_plane_events_off():
    """A process of a session whose ``plane_events`` is off imports jax:
    no listener of ours, no hook on the import, no row."""
    code = (
        "import ray_tpu, jax, jax.numpy as jnp\n"
        "from jax._src import monitoring as m\n"
        "from ray_tpu._private import jax_platform as jp\n"
        "from ray_tpu.util import events\n"
        "jax.jit(lambda x: x + 1)(jnp.ones(2))\n"
        "ours = [l for l in m.get_event_duration_listeners() "
        "+ m.get_event_listeners() if l.__module__ == jp.__name__]\n"
        "print('listeners', len(ours), 'recording', jp._recording, "
        "'pending', events.pending())\n")
    env = {**os.environ, "RAY_TPU_PLANE_EVENTS": "0", "JAX_PLATFORMS": "cpu"}
    env.pop("RAY_TPU_JAX_PLATFORM", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120)
    assert "listeners 0 recording False pending 0" in out.stdout, out
    env["RAY_TPU_PLANE_EVENTS"] = "1"
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120)
    assert "listeners 2 recording True" in out.stdout, out


# ------------------------------------------------------ the node's probe
class _Conn:
    closed = False

    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


@pytest.mark.parametrize("child, chips, rc", [
    ("print(4)", 4, 0), ("raise SystemExit(3)", 0, 3)])
def test_probe_span_carries_chips_and_return_code(ring, tmp_path, monkeypatch,
                                                  child, chips, rc):
    from ray_tpu._private import node

    monkeypatch.setattr(node, "_TPU_PROBE", child)
    monkeypatch.setattr(node, "session_pinned_off_tpu", lambda: False)
    agent = node.NodeAgent.__new__(node.NodeAgent)
    agent.session_dir, agent.conn = str(tmp_path), _Conn()
    agent.node_id = node.NodeID.from_random()
    t0 = time.perf_counter_ns()
    asyncio.run(agent._probe_tpu())
    rows, _ = events.drain()
    (row,) = [events.row_to_dict(r) for r in rows
              if r[1] == "gcs.node.probe"]
    assert row["fields"]["chips"] == chips and row["fields"]["rc"] == rc
    assert row["fields"]["t0_ns"] >= t0 and row["fields"]["dur_ns"] > 0
    sent = [m for m in agent.conn.sent if m["t"] == "update_resources"]
    assert len(sent) == (1 if chips else 0)
    assert os.path.exists(tmp_path / "tpu_probe.out")   # the child's words


def test_pinned_session_probes_nothing_and_writes_no_row(ring, tmp_path,
                                                         monkeypatch):
    from ray_tpu._private import node

    monkeypatch.setattr(node, "session_pinned_off_tpu", lambda: True)
    agent = node.NodeAgent.__new__(node.NodeAgent)
    agent.session_dir, agent.conn = str(tmp_path), _Conn()
    asyncio.run(agent._probe_tpu())
    assert events.pending() == 0


# ------------------------------------------------- one drain for everyone
def test_one_helper_delivers_and_spills(ring, tmp_path):
    frames = []
    with events.span("gcs.cluster.start", "gcs", started_head=True):
        pass
    n = events.drain_and_spill(frames.append, str(tmp_path), nid=b"\x07")
    assert n == 1 and events.pending() == 0
    (frame,) = frames
    assert frame["t"] == "plane_events" and frame["pid"] == os.getpid()
    assert frame["nid"] == b"\x07" and len(frame["ev"]) == 1
    back = events.read_spill(session_dir=str(tmp_path), pid=os.getpid())
    assert [r["name"] for r in back] == ["gcs.cluster.start"]
    assert events.drain_and_spill(frames.append, str(tmp_path)) == 0


def test_a_lost_connection_costs_the_frame_not_the_file(ring, tmp_path):
    def broken(frame):
        raise ConnectionError("gone")

    with events.span("gcs.driver.connect", "gcs"):
        pass
    assert events.drain_and_spill(broken, str(tmp_path)) == 1
    assert len(events.read_spill(session_dir=str(tmp_path),
                                 pid=os.getpid())) == 1


def test_on_a_loop_the_append_goes_to_the_executor(ring, tmp_path):
    async def flush():
        with events.span("gcs.head.spawn", "gcs"):
            pass
        events.drain_and_spill(lambda frame: None, str(tmp_path))
        await events.spilled(timeout=5)
        return events.read_spill(session_dir=str(tmp_path), pid=os.getpid())

    assert [r["name"] for r in asyncio.run(flush())] == ["gcs.head.spawn"]


def test_process_start_is_before_now_and_after_boot():
    start = events.process_start_ns()
    assert 0 < start < time.perf_counter_ns()
    assert time.perf_counter_ns() - start < 24 * 3600 * 10**9


def test_process_actor_is_empty_outside_an_actor():
    assert events.process_actor() == {}
