"""Serve tests (model: reference ``python/ray/serve/tests``)."""

import json
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def serve_cluster(ray_cluster):
    yield ray_cluster
    serve.shutdown()


def test_basic_deployment(serve_cluster):
    @serve.deployment
    class Echo:
        def __call__(self, x):
            return {"echo": x}

    handle = serve.run(Echo.bind(), name="echo-app", route_prefix=None)
    assert handle.remote("hi").result(timeout=30) == {"echo": "hi"}


def test_function_deployment(serve_cluster):
    @serve.deployment
    def square(x):
        return x * x

    handle = serve.run(square.bind(), name="fn-app", route_prefix=None)
    assert handle.remote(7).result(timeout=30) == 49


def test_multiple_replicas_all_serve(serve_cluster):
    @serve.deployment(num_replicas=3)
    class Pid:
        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(Pid.bind(), name="pid-app", route_prefix=None)
    pids = {handle.remote(None).result(timeout=30) for _ in range(20)}
    assert len(pids) >= 2  # pow-2 routing spreads load


def test_method_call(serve_cluster):
    @serve.deployment
    class Multi:
        def __init__(self):
            self.n = 0

        def incr(self, k):
            self.n += k
            return self.n

        def value(self):
            return self.n

    handle = serve.run(Multi.bind(), name="multi-app", route_prefix=None)
    handle.incr.remote(5).result(timeout=30)
    # num_replicas=1 so state accumulates on the single replica
    assert handle.value.remote().result(timeout=30) == 5


def test_composition(serve_cluster):
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, doubler):
            self.doubler = doubler

        async def __call__(self, x):
            return await self.doubler.remote(x) + 1

    handle = serve.run(Ingress.bind(Doubler.bind()), name="comp-app",
                       route_prefix=None)
    assert handle.remote(10).result(timeout=30) == 21


def test_http_ingress(serve_cluster):
    import requests

    @serve.deployment
    class Api:
        async def __call__(self, request):
            body = request.json()
            return {"sum": body["a"] + body["b"], "path": request.path}

    serve.run(Api.bind(), name="http-app", route_prefix="/api")
    port = serve.get_proxy_port()
    assert port
    r = requests.post(f"http://127.0.0.1:{port}/api/add",
                      data=json.dumps({"a": 2, "b": 3}), timeout=30)
    assert r.status_code == 200
    assert r.json() == {"sum": 5, "path": "/api/add"}


def test_http_404(serve_cluster):
    import requests

    port = serve.get_proxy_port()
    r = requests.get(f"http://127.0.0.1:{port + 1 if False else port}"
                     "/definitely-not-routed-xyz", timeout=30)
    # "/" prefix may catch it; tolerate either 404 (no app) or routed 500/200
    assert r.status_code in (200, 404, 500)


def test_batching(serve_cluster):
    @serve.deployment
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        async def handle(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        async def __call__(self, x):
            return await self.handle(x)

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), name="batch-app", route_prefix=None)
    responses = [handle.remote(i) for i in range(8)]
    outs = sorted(r.result(timeout=30) for r in responses)
    assert outs == [i * 10 for i in range(8)]
    sizes = handle.sizes.remote().result(timeout=30)
    assert max(sizes) > 1  # batching actually batched


def test_reconfigure_user_config(serve_cluster):
    @serve.deployment(user_config={"mult": 3})
    class Conf:
        def __init__(self):
            self.mult = 1

        def reconfigure(self, cfg):
            self.mult = cfg["mult"]

        def __call__(self, x):
            return x * self.mult

    handle = serve.run(Conf.bind(), name="conf-app", route_prefix=None)
    assert handle.remote(5).result(timeout=30) == 15


def test_status_and_delete(serve_cluster):
    @serve.deployment
    def noop(x):
        return x

    serve.run(noop.bind(), name="temp-app", route_prefix=None)
    assert "temp-app" in serve.status()
    serve.delete("temp-app")
    assert "temp-app" not in serve.status()


def test_config_push_invalidates_handle_cache(serve_cluster):
    """Long-poll-equivalent (reference serve/_private/long_poll.py): after
    the controller scales a deployment, existing handles see the new
    replica set without manual refresh or per-request polling."""
    import time

    from ray_tpu import serve
    from ray_tpu.serve.controller import get_controller

    @serve.deployment(num_replicas=1)
    class Who:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, req):
            return self.pid

    serve.run(Who.bind(), name="who_app", route_prefix=None)
    h = serve.get_deployment_handle("Who", "who_app")
    first = {h.remote(None).result() for _ in range(4)}
    assert len(first) == 1  # one replica

    ctl = get_controller()
    import ray_tpu as rt

    rt.get(ctl.scale.remote("who_app", "Who", 3))
    # the push arrives asynchronously; the handle must converge without
    # any explicit refresh call
    deadline = time.time() + 20
    seen = set()
    while time.time() < deadline:
        seen.add(h.remote(None).result())
        if len(seen) >= 2:
            break
        time.sleep(0.1)
    assert len(seen) >= 2, f"handle never saw scaled replicas: {seen}"


def test_handle_retries_on_dead_replica(serve_cluster):
    """A request landing on a killed replica retries on a live one
    (reference: router failure rescheduling, pow_2_scheduler)."""
    from ray_tpu import serve
    from ray_tpu.serve.controller import get_controller

    @serve.deployment(num_replicas=2)
    class Who:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, req):
            return self.pid

    serve.run(Who.bind(), name="retry_app", route_prefix=None)
    h = serve.get_deployment_handle("Who", "retry_app")
    h.remote(None).result()  # resolve replicas

    # Kill one replica out from under the handle's cache, then hammer:
    # every request must still succeed (dead-replica hits retry).
    ctl = get_controller()
    import ray_tpu as rt

    replicas = rt.get(ctl.get_replicas.remote("retry_app", "Who"))
    rt.kill(replicas[0])
    results = [h.remote(None).result(timeout=30) for _ in range(10)]
    assert all(isinstance(r, int) for r in results)


def test_health_loop_waits_for_a_replica_still_in_its_constructor(
        serve_cluster, tmp_path):
    """A replica answers nothing until its constructor returns (an LLM
    replica's builds weights for minutes). The health loop must treat it
    as STARTING — not time a 5 s probe, drop it and spawn a rival that
    then wants the same chip. The constructor here outlasts one health
    period (10 s) plus the probe's timeout (5 s)."""
    marker = tmp_path / "constructed"

    @serve.deployment
    class SlowStart:
        def __init__(self, marker):
            import os
            import time

            with open(marker, "a") as f:
                f.write(f"{os.getpid()}\n")
            time.sleep(16)

        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(SlowStart.bind(str(marker)), name="slow-app",
                       route_prefix=None)
    pid = handle.remote(None).result(timeout=30)
    time.sleep(1.0)   # a rival's constructor would have signed in by now
    assert marker.read_text().split() == [str(pid)]   # one constructor ran
    serve.delete("slow-app")
