"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: serve, hand-over, train
    python chip_smoke.py --chips 4  # one host with four: the sharded phase only

Drives the compute tier's two normal paths through the entry points a user
calls, at the full width and depth of ``LLAMA3_1B`` with seeded random
weights, in ONE cluster session started by ``ray_tpu.init()``:

  serve      ``serve.run`` of ``serve.llm.LLMServer`` (paged KV cache) — ten
             concurrent requests that hit every prefill bucket, one streamed;
             one output checked in the replica against the model's own full
             ``forward``.
  hand-over  the application is shut down; the replica's process must be
             gone and the scheduler's ``TPU`` free before training asks.
  train      ``JaxTrainer`` with one TPU worker: AdamW on 2x2048 seeded
             tokens, one ``report()`` with a checkpoint.

This process stays off jax: a driver that initialises a backend holds the
chip, and the worker the scheduler granted it to then fails. Everything it
``get``s is Python and NumPy. It exports no platform pin either — children
would inherit it. It needs a TPU: without one it exits non-zero and prints
no result line. ``--rehearse`` walks the same phases at a toy width on
whatever jax finds (the CPU, here) to check paths and control flow; its
last line is never ``"ok": true``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``, with
the device as the process that held the chip reported it.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import random
import sys
import threading
import time

import ray_tpu
from ray_tpu import serve
from ray_tpu._private.jax_platform import compile_cache_dir
from ray_tpu._private.node import session_pinned_off_tpu
from ray_tpu.serve.llm import LLMServer
from ray_tpu.train import Checkpoint, JaxTrainer, RunConfig, ScalingConfig

SEED = 0
#: the driver draws prompts without importing the model (that imports jax)
LLAMA3_VOCAB, TOY_VOCAB = 128256, 512
_HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_HERE, "chiprun_out", "chip_smoke")
DEFAULT_CACHE = os.path.join(_HERE, ".jax_cache")

# serve: 8 slots over a pool that holds every request below at once
MAX_LEN, PAGE, NUM_PAGES, SLOTS = 2048, 16, 2048, 8
#: one prompt per prefill bucket (16, 64, 256, and the max_len bucket) —
#: the warm-up wave compiles every program the steady wave will use
WARMUP_PROMPTS = (12, 48, 200, 1024)
STEADY_PROMPTS = (12, 48, 128, 200, 256, 300, 512, 700, 900, 1024)
#: an emitted token's logit may sit this far under that position's argmax
#: in the full forward (bf16, random weights, 128k-way near-ties)
LOGIT_MARGIN = 0.5
HEALTH_TIMEOUT_S = 5.0   # serve/controller.py's probe timeout

# train
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 5

# --chips 4
FOUR_BATCH, FOUR_SEQ, FOUR_STEPS = 4, 1024, 4
# LLAMA3_8B widths, depth sized from memory_analysis() of AOT compiles for
# a described v5e:2x2: 2 layers are 8.92 GB of params + AdamW state and
# 3.27 GB of temporaries on one device; 26 layers need all four (about
# 10.1 GB of state and 3.4 GB of temporaries on each).
FOUR_DEPTH_ONE_DEVICE = 2
FOUR_DEPTH_SHARDED = 26
FOUR_LOSS_TOL = 0.1            # |loss_1dev - loss_2x2| per step, bf16
FOUR_BALANCE = 0.10            # no device above the mean bytes by more
RING_SEQ, RING_TOL = 8192, 3e-2


def say(msg: str):
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def check(ok: bool, what: str):
    """A phase that fails fails the run."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")
    say(f"ok: {what}")


def check_on_chip(rehearse: bool, ok: bool, what: str):
    """What only a TPU can show; a rehearsal says that it looked away."""
    if rehearse:
        say(f"not checked off the chip: {what}")
    else:
        check(ok, what)


def smoke_cfg(name: str, rehearse: bool, n_layers: int = 0):
    """The published widths — or, rehearsing, a toy with the same ratios."""
    from ray_tpu.models.llama import LLAMA3_1B, LLAMA3_8B

    cfg = {"1b": LLAMA3_1B, "8b": LLAMA3_8B}[name]
    if rehearse:
        cfg = dataclasses.replace(cfg, d_model=128, n_heads=4, n_kv_heads=2,
                                  d_ff=256, vocab_size=TOY_VOCAB, n_layers=2)
        n_layers = min(n_layers, 3)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    assert cfg.vocab_size == (TOY_VOCAB if rehearse else LLAMA3_VOCAB)
    return cfg


# ------------------------------------------------------------ in the replica

def model_factory(rehearse: bool):
    def factory():
        import jax

        from ray_tpu.models.llama import init_params

        cfg = smoke_cfg("1b", rehearse)
        params = init_params(cfg, jax.random.PRNGKey(SEED))
        jax.block_until_ready(params)
        return params, cfg

    return factory


class CheckedLLMServer(LLMServer):
    """``LLMServer`` plus one admin op that runs IN the replica, where the
    weights and the chip are: the full forward over prompt + output."""

    async def __call__(self, request):
        body = self._body(request)
        if body.get("_admin") == "check_forward":
            # off the event loop: the forward compiles for a while
            return await asyncio.get_running_loop().run_in_executor(
                None, self._check_forward, body["prompt"], body["tokens"])
        return await super().__call__(request)

    def _check_forward(self, prompt, emitted):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.llama import forward

        seq = list(prompt) + list(emitted)
        # causal, so right-padding to the flash kernel's 128-row tiling
        # changes nothing at the positions read below
        padded = seq + [0] * (-len(seq) % 128)
        with self._engine_lock:
            params = self.engine.params
        logits = jax.jit(forward, static_argnames=("cfg",))(
            params, jnp.asarray([padded], jnp.int32), cfg=self._cfg)
        # row i predicts token i+1: the rows that predicted `emitted`
        rows = np.asarray(logits[0, len(prompt) - 1:len(seq) - 1]
                          .astype(jnp.float32))
        chosen = rows[np.arange(len(emitted)), np.asarray(emitted)]
        margins = rows.max(axis=-1) - chosen
        return {"max_margin": float(margins.max()),
                "exact_argmax": int((margins == 0).sum()),
                "tokens": len(emitted),
                "finite": bool(np.isfinite(rows).all())}


# ------------------------------------------------------- in the train worker

def make_train_step(cfg, opt, attn_impl=None):
    import jax
    import optax

    from ray_tpu.models.llama import loss_fn

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(
            p, {"tokens": tokens}, cfg, attn_impl=attn_impl))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def seeded_tokens(cfg, batch: int, seq: int):
    import numpy as np

    return np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32)


def run_steps(compiled, params, opt_state, tokens, n: int):
    """n steps, each closed by block_until_ready -> (losses, seconds)."""
    import jax

    losses, seconds = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens)
        jax.block_until_ready((params, opt_state, loss))
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, seconds


def train_loop(config):
    import tempfile

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import init_params
    from ray_tpu.train.checkpoint import save_pytree

    cfg = smoke_cfg("1b", config["rehearse"])
    opt = optax.adamw(3e-4, weight_decay=0.1)
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    opt_state = opt.init(params)
    tokens = jax.device_put(seeded_tokens(cfg, TRAIN_BATCH, TRAIN_SEQ))
    jax.block_until_ready((params, opt_state, tokens))
    weights_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1)).lower(
        params, opt_state, tokens)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    params, opt_state, losses, seconds = run_steps(
        compiled, params, opt_state, tokens, 1 + TRAIN_STEPS)
    # The checkpoint is the final norm and the loss curve, not 9 GB of
    # state: the output directory may bring back 64 MiB.
    ckpt = tempfile.mkdtemp()
    save_pytree({"norm": params["norm"], "losses": np.asarray(losses)}, ckpt)
    train.report({"losses": losses, "step_seconds": seconds,
                  "weights_s": weights_s, "compile_s": compile_s,
                  "has_tpu_custom_call": has_kernel,
                  "params": cfg.param_count()},
                 checkpoint=Checkpoint.from_directory(ckpt))


def sharded_state(cfg, opt, mesh):
    """Abstract params and optimizer state with the repo's sharding rules:
    ``(abstract_params, param_shardings, abstract_opt, opt_shardings)``."""
    import jax

    from ray_tpu.models.llama import init_params
    from ray_tpu.parallel.sharding import (optimizer_shardings,
                                           shardings_for_tree)

    abstract_params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(SEED)))
    param_sh = shardings_for_tree(abstract_params, mesh)
    abstract_opt = jax.eval_shape(opt.init, abstract_params)
    opt_sh = jax.tree.map(lambda s: s.sharding, optimizer_shardings(
        abstract_params, param_sh, abstract_opt, mesh))
    return abstract_params, param_sh, abstract_opt, opt_sh


def sharded_train(cfg, mesh, steps: int):
    """Create params and AdamW state SHARDED (never whole on one device),
    run ``steps`` on the seeded batch -> (losses, seconds, bytes/device)."""
    import jax
    import optax

    from ray_tpu.models.llama import init_params
    from ray_tpu.ops.attention import make_flash_attention
    from ray_tpu.parallel.mesh import batch_sharding

    opt = optax.adamw(3e-4, weight_decay=0.1)
    _, param_sh, _, opt_sh = sharded_state(cfg, opt, mesh)
    params = jax.jit(lambda: init_params(cfg, jax.random.PRNGKey(SEED)),
                     out_shardings=param_sh)()
    opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
    tokens = jax.device_put(seeded_tokens(cfg, FOUR_BATCH, FOUR_SEQ),
                            batch_sharding(mesh))
    jax.block_until_ready((params, opt_state, tokens))
    t0 = time.perf_counter()
    # XLA cannot partition a Mosaic kernel: under a mesh the flash kernel
    # runs per device, on that device's batch rows and heads. The mesh-bound
    # attention also tells forward_hidden that the step is sharded, which
    # then holds the residual stream to the batch axes (ZeRO-3 x Megatron)
    step = make_train_step(cfg, opt, make_flash_attention(mesh))
    lowered = jax.jit(step, donate_argnums=(0, 1),
                      out_shardings=(param_sh, opt_sh, None)).lower(
        params, opt_state, tokens)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    params, opt_state, losses, seconds = run_steps(
        compiled, params, opt_state, tokens, steps)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat]
    del params, opt_state
    return {"losses": losses, "step_seconds": seconds, "compile_s": compile_s,
            "bytes_in_use": in_use, "has_tpu_custom_call": has_kernel,
            "params": cfg.param_count(), "n_layers": cfg.n_layers}


def attention_value_and_grads(attn):
    """jitted ``(q, k, v, w) -> ((sum(out * w), out), (dq, dk, dv))``."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v, w):
        out = attn(q, k, v)
        return (out.astype(jnp.float32) * w).sum(), out

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))


def ring_vs_dense(mesh, rehearse: bool):
    """ring_attention(sp=4), forward and gradient, against dense_attention
    on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import dense_attention
    from ray_tpu.parallel.ring_attention import make_ring_attention

    B, L, H, Hk, D = 1, (512 if rehearse else RING_SEQ), 8, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, w = (np.asarray(jax.random.normal(
        key, (B, L, h, D), jnp.float32)).astype(jnp.bfloat16)
        for key, h in zip(keys, (H, Hk, Hk, H)))
    ring = make_ring_attention(mesh, causal=True, block_impl="flash")
    seq_sh = NamedSharding(mesh, P(None, "sp", None, None))
    sharded = [jax.device_put(x, seq_sh) for x in (q, k, v, w)]
    ring_fn = attention_value_and_grads(ring)
    has_kernel = "tpu_custom_call" in ring_fn.lower(*sharded).as_text()
    (_, out_r), grads_r = ring_fn(*sharded)
    one = mesh.devices.flat[0]
    single = [jax.device_put(x, one) for x in (q, k, v, w)]
    (_, out_d), grads_d = attention_value_and_grads(
        lambda q, k, v: dense_attention(q, k, v, causal=True))(*single)

    def rel_err(a, b):
        a, b = (np.asarray(x).astype(np.float32) for x in (a, b))
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    return {"seq": L, "has_tpu_custom_call": has_kernel,
            "out_rel_err": rel_err(out_r, out_d),
            "grad_rel_err": [rel_err(r, d)
                             for r, d in zip(grads_r, grads_d)]}


def four_chip_loop(config):
    import jax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    rehearse = config["rehearse"]
    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs four devices, jax found "
                           f"{len(devices)}: {devices}")
    ring = ring_vs_dense(make_mesh(MeshSpec(sp=4), devices), rehearse)
    small = smoke_cfg("8b", rehearse, FOUR_DEPTH_ONE_DEVICE)
    one = sharded_train(small, make_mesh(MeshSpec(), devices[:1]),
                        FOUR_STEPS)
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices)
    four = sharded_train(small, mesh, FOUR_STEPS)
    deep = sharded_train(smoke_cfg("8b", rehearse, FOUR_DEPTH_SHARDED),
                         mesh, 2)
    train.report({"ring": ring, "one_device": one, "fsdp2_tp2": four,
                  "deep": deep})


# ------------------------------------------------------------- in the driver

def _descendants() -> set:
    """This process and every process under it (the whole session)."""
    parent = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                parent[int(pid)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue   # exited while we looked
    tree = {os.getpid()}
    grew = True
    while grew:
        more = {p for p, pp in parent.items() if pp in tree} - tree
        tree |= more
        grew = bool(more)
    return tree


def tpu_backend_holders() -> set:
    """pids of this session that have libtpu mapped: whoever loaded the TPU
    backend, by the kernel's account and not the process's own."""
    holders = set()
    for pid in _descendants():
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" in f.read():
                    holders.add(pid)
        except OSError:
            continue
    return holders


class Watch(threading.Thread):
    """Samples, for the length of a phase, who holds the TPU backend — and,
    given a handle, how long the replica takes to answer a stats request
    (the health loop gives it HEALTH_TIMEOUT_S)."""

    def __init__(self, handle=None):
        super().__init__(daemon=True)
        self.handle = handle
        self.holders: set = set()
        self.worst_ping_s = 0.0
        self.pings = 0
        self.error = None
        self._done = threading.Event()

    def run(self):
        try:
            while not self._done.wait(1.0):
                self.holders |= tpu_backend_holders()
                if self.handle is not None:
                    t0 = time.perf_counter()
                    self.handle.remote({"_admin": "stats"}).result(
                        timeout=120)
                    self.worst_ping_s = max(self.worst_ping_s,
                                            time.perf_counter() - t0)
                    self.pings += 1
        except Exception as e:  # noqa: BLE001 — raised by __exit__
            self.error = e

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self._done.set()
        self.join()
        if self.error is not None and exc[0] is None:
            raise SystemExit(f"chip_smoke FAILED: the watcher died: "
                             f"{self.error!r}")


def cache_entries() -> int:
    """Compiled programs in the cache (with a size cap set from outside,
    jax keeps a ``-cache`` and an ``-atime`` file for each)."""
    d = compile_cache_dir()
    names = os.listdir(d) if os.path.isdir(d) else []
    return len({n.removesuffix("-cache").removesuffix("-atime")
                for n in names})


def wait_for_chips(n: int, rehearse: bool):
    """The node's probe runs in a child of the head and reports once that
    child has exited; nothing is deployed before its count is in
    ``cluster_resources()``."""
    if rehearse:
        return
    check(not session_pinned_off_tpu(),
          "no platform pin in this environment keeps the session off the "
          "TPU (RAY_TPU_JAX_PLATFORM="
          f"{os.environ.get('RAY_TPU_JAX_PLATFORM')!r}, JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS')!r})")
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 180:
        if ray_tpu.cluster_resources().get("TPU", 0) >= 1:
            break
        time.sleep(0.5)
    found = ray_tpu.cluster_resources().get("TPU", 0)
    say(f"chip probe: TPU={found} after {time.perf_counter() - t0:.1f}s")
    check(found == n, f"the node reports {n} TPU chip(s) (found {found})")


def requests_for(lengths, cfg_vocab: int, rng: random.Random, new=None):
    return [{"prompt": [rng.randrange(cfg_vocab) for _ in range(n)],
             "max_new_tokens": new or rng.randint(32, 64)} for n in lengths]


def send_all(handle, bodies, stream_index=None):
    """All at once through the deployment handle -> token lists."""
    async def streamed(body):
        return [t async for t in handle.stream({**body, "stream": True})]

    futures = [None if i == stream_index else handle.remote(b)
               for i, b in enumerate(bodies)]
    out = [None] * len(bodies)
    if stream_index is not None:
        out[stream_index] = asyncio.run(streamed(bodies[stream_index]))
    for i, f in enumerate(futures):
        if f is not None:
            out[i] = f.result(timeout=900)["tokens"]
    return out


def serve_phase(rehearse: bool) -> dict:
    vocab = TOY_VOCAB if rehearse else LLAMA3_VOCAB
    rng = random.Random(SEED)
    t0 = time.perf_counter()
    app = serve.deployment(
        CheckedLLMServer, ray_actor_options={"num_tpus": 1}).bind(
        model_factory(rehearse), max_slots=SLOTS, max_len=MAX_LEN,
        num_pages=NUM_PAGES, page_size=PAGE)
    handle = serve.run(app, name="chip_smoke", route_prefix=None)
    weights_s = time.perf_counter() - t0
    stats = handle.remote({"_admin": "stats"}).result(timeout=120)
    device = stats["device"]
    say(f"serve: replica pid {device['pid']} on {device['platform']} "
        f"({device['device_kind']} x{device['device_count']}); "
        f"weights + engine in {weights_s:.1f}s")
    check_on_chip(rehearse, device["platform"] == "tpu",
                  f"the replica computes on the chip (it says "
                  f"{device['platform']!r})")
    controller = serve.get_controller()

    def replica_ids():
        return [r._id.hex() for r in ray_tpu.get(
            controller.get_replicas.remote("chip_smoke",
                                           "CheckedLLMServer"))]

    replicas = replica_ids()
    check(len(replicas) == 1, "one replica behind the deployment")

    with Watch(handle) as watch:
        t0 = time.perf_counter()
        send_all(handle, requests_for(WARMUP_PROMPTS, vocab, rng, new=4))
        compile_s = time.perf_counter() - t0
        say(f"serve: first compile of every program (4 prefill buckets, "
            f"the decode step, the page scatter) in {compile_s:.1f}s")

        bodies = requests_for(STEADY_PROMPTS, vocab, rng)
        t0 = time.perf_counter()
        outs = send_all(handle, bodies, stream_index=3)
        steady_s = time.perf_counter() - t0
        n_tokens = sum(len(o) for o in outs)
        for body, toks in zip(bodies, outs):
            check(len(toks) == body["max_new_tokens"]
                  and all(isinstance(t, int) and 0 <= t < vocab
                          for t in toks),
                  f"prompt of {len(body['prompt'])}: "
                  f"{body['max_new_tokens']} in-vocabulary tokens")
        say(f"serve: {len(bodies)} concurrent requests "
            f"(one streamed), {n_tokens} tokens in {steady_s:.2f}s steady")

        again = send_all(handle, [bodies[5]])[0]
        twice = send_all(handle, [bodies[5]])[0]
        check(again == twice, "the same greedy request twice gives the "
                              "same tokens")
        t0 = time.perf_counter()
        fwd = handle.remote({"_admin": "check_forward",
                             "prompt": bodies[5]["prompt"],
                             "tokens": again}).result(timeout=900)
        say(f"serve: full forward over prompt+output in "
            f"{time.perf_counter() - t0:.1f}s (compile included): {fwd}")
        check(fwd["finite"] and fwd["max_margin"] <= LOGIT_MARGIN,
              f"every emitted token within {LOGIT_MARGIN} logits of the "
              f"full forward's argmax (worst {fwd['max_margin']:.4f}, "
              f"{fwd['exact_argmax']}/{fwd['tokens']} exactly the argmax)")

    stats = handle.remote({"_admin": "stats"}).result(timeout=120)
    check(stats["device"]["pid"] == device["pid"]
          and replica_ids() == replicas,
          "the controller replaced no replica: same actor, same pid")
    check(watch.pings > 0 and watch.worst_ping_s < HEALTH_TIMEOUT_S,
          f"the replica answered {watch.pings} probes through every "
          f"compile and prefill, the slowest in {watch.worst_ping_s:.2f}s "
          f"(the health loop allows {HEALTH_TIMEOUT_S}s)")
    check_on_chip(rehearse, watch.holders == {device["pid"]},
                  f"only the replica loaded the TPU backend while serving "
                  f"(pids with libtpu mapped: {sorted(watch.holders)})")
    peak = stats["device"]["peak_bytes_in_use"]
    say(f"serve: peak_bytes_in_use {peak}")
    return {"device": stats["device"], "weights_s": weights_s,
            "compile_s": compile_s, "steady_s": steady_s,
            "tokens": n_tokens}


def hand_over(pid: int, chips: float, what: str):
    """The chip is free for the next phase only when the process that held
    it is gone AND the scheduler has its ``TPU`` back."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 60:
        gone = not os.path.exists(f"/proc/{pid}")
        free = ray_tpu.available_resources().get("TPU", 0) == chips
        if gone and free:
            break
        time.sleep(0.1)
    check(gone, f"{what}'s process {pid} has exited")
    check(free, f"the scheduler's TPU is free again "
                f"({ray_tpu.available_resources().get('TPU', 0)} of "
                f"{chips}) {time.perf_counter() - t0:.1f}s later")


def fit(loop, chips: int, rehearse: bool, name: str):
    trainer = JaxTrainer(
        loop, train_loop_config={"rehearse": rehearse},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=chips),
        run_config=RunConfig(name=name,
                             storage_path=os.path.join(OUT_DIR, "train")))
    with Watch() as watch:
        result = trainer.fit()
    if result.error is not None:
        raise SystemExit(f"chip_smoke FAILED: {name}: {result.error}")
    device = result.metrics["device"]
    say(f"{name}: worker pid {device['pid']} on {device['platform']} "
        f"({device['device_kind']} x{device['device_count']})")
    check_on_chip(rehearse, device["platform"] == "tpu"
                  and device["device_count"] == chips,
                  f"the train worker computes on {chips} chip(s)")
    check_on_chip(rehearse, watch.holders == {device["pid"]},
                  f"only the train worker loaded the TPU backend while "
                  f"training (pids with libtpu mapped: "
                  f"{sorted(watch.holders)})")
    return result, device


def train_phase(rehearse: bool) -> dict:
    result, device = fit(train_loop, 1, rehearse, "train")
    m = result.metrics
    losses = m["losses"]
    say(f"train: {m['params'] / 1e9:.2f} B params, weights "
        f"{m['weights_s']:.1f}s, compile {m['compile_s']:.1f}s; "
        f"losses {[round(x, 4) for x in losses]}")
    steady = m["step_seconds"][1:]
    say(f"train: {len(steady)} steps after the first in "
        f"{sum(steady):.3f}s ({TRAIN_BATCH}x{TRAIN_SEQ} tokens each), "
        f"peak_bytes_in_use {device['peak_bytes_in_use']}")
    check(all(x == x and abs(x) != float("inf") for x in losses)
          and losses[-1] < losses[0],
          f"losses finite and falling ({losses[0]:.4f} -> {losses[-1]:.4f})")
    check(result.checkpoint is not None
          and os.path.isdir(result.checkpoint.path),
          f"checkpoint directory {result.checkpoint.path}")
    check_on_chip(rehearse, m["has_tpu_custom_call"],
                  "the step's lowered text holds a tpu_custom_call (flash, "
                  "not dense)")
    return device


def four_chip_phase(rehearse: bool) -> dict:
    result, device = fit(four_chip_loop, 4, rehearse, "four_chips")
    m = result.metrics
    ring = m["ring"]
    say(f"ring_attention sp=4 L{ring['seq']}: {ring}")
    check_on_chip(rehearse, ring["has_tpu_custom_call"],
                  "ring attention ran the stats kernel, not the dense block")
    check(ring["out_rel_err"] < RING_TOL
          and max(ring["grad_rel_err"]) < RING_TOL,
          f"ring forward and (dq, dk, dv) within {RING_TOL} of "
          f"dense_attention on one device")
    one, four, deep = m["one_device"], m["fsdp2_tp2"], m["deep"]
    for name, run in (("one device", one), ("fsdp=2,tp=2", four),
                      ("fsdp=2,tp=2 deep", deep)):
        say(f"{name}: depth {run['n_layers']}, "
            f"{run['params'] / 1e9:.2f} B params, compile "
            f"{run['compile_s']:.1f}s, losses "
            f"{[round(x, 4) for x in run['losses']]}, step seconds "
            f"{[round(x, 3) for x in run['step_seconds']]}, bytes in use "
            f"per device {run['bytes_in_use']}")
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    check(len(diffs) >= 4 and max(diffs) <= FOUR_LOSS_TOL,
          f"per-step losses of one device and fsdp=2,tp=2 agree within "
          f"{FOUR_LOSS_TOL} (worst {max(diffs):.5f})")
    check(all(x == x for x in deep["losses"]),
          "the four-chip depth trains: finite losses")
    used = deep["bytes_in_use"]   # the CPU backend reports none
    check_on_chip(rehearse, None not in used and max(used) <= (
        sum(used) / len(used)) * (1 + FOUR_BALANCE),
        f"no device holds more than {FOUR_BALANCE:.0%} above the mean "
        f"bytes in use ({used})")
    check_on_chip(rehearse, four["has_tpu_custom_call"]
                  and deep["has_tpu_custom_call"],
                  "the sharded step holds the flash kernel")
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on whatever jax finds; never ok")
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)
    before = cache_entries()
    say(f"compile cache {compile_cache_dir()}: {before} entries")
    t_start = time.perf_counter()
    # Rehearsing, the chips are declared (there are none to probe) and the
    # workers inherit whatever platform this shell is held to.
    ray_tpu.init(num_tpus=args.chips if args.rehearse else None)
    try:
        from ray_tpu._private.worker import global_worker

        say(f"object store: {type(global_worker().store).__name__}")
        wait_for_chips(args.chips, args.rehearse)
        if args.chips == 4:
            device = four_chip_phase(args.rehearse)
        else:
            served = serve_phase(args.rehearse)
            serve.shutdown()
            hand_over(served["device"]["pid"], 1.0, "the replica")
            device = train_phase(args.rehearse)
        hand_over(device["pid"], float(args.chips), "the train worker")
        check("jax" not in sys.modules
              and os.getpid() not in tpu_backend_holders(),
              "the driver never imported jax nor loaded the TPU backend")
    finally:
        ray_tpu.shutdown()
    say(f"compile cache {compile_cache_dir()}: {before} entries before, "
        f"{cache_entries()} after; whole run "
        f"{time.perf_counter() - t_start:.1f}s")
    if compile_cache_dir() != DEFAULT_CACHE:
        check(not os.path.exists(DEFAULT_CACHE),
              f"the cache was placed from outside: no {DEFAULT_CACHE}")
    print(json.dumps({
        "ok": not args.rehearse,
        **({"rehearsal": "passed"} if args.rehearse else {}),
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["device_count"]}}))


if __name__ == "__main__":
    main()
