"""What runs in the train worker: the sharded AdamW step under
``JaxTrainer.fit``, timed, traced and checked where the chips are."""

from __future__ import annotations

import contextlib
import os
import shutil
import time

#: |sharded bf16 step's first loss - float32 reference's loss| at depth 2 on
#: the same tokens and weights. The loss is a mean over thousands of
#: positions of log-softmax values near ln(vocab) ~ 10: bf16's 2**-9 relative
#: rounding of logits of magnitude <= 8 is <= 0.016 each and largely cancels
#: in the mean (PR 21 measured 0.001 between two bf16 layouts). An 8-bit path
#: moves single logits by 0.1-0.5 and the mean loss by several hundredths.
LOSS_TOL = 0.02


def abstract_state(config, cfg, mesh, opt):
    """Shapes of parameters and AdamW state with the repo's sharding rules:
    ``(abstract_params, param_shardings, abstract_opt, opt_shardings)``."""
    import jax

    from ray_tpu.parallel.sharding import (optimizer_shardings,
                                           shardings_for_tree)

    from perfbench.manifest import resolve

    init = resolve(config["program"]["init_params"])
    abstract = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    param_sh = shardings_for_tree(abstract, mesh)
    abstract_opt = jax.eval_shape(opt.init, abstract)
    opt_sh = jax.tree.map(lambda s: s.sharding, optimizer_shardings(
        abstract, param_sh, abstract_opt, mesh))
    return abstract, param_sh, abstract_opt, opt_sh


def _state(config, cfg, mesh, seed, opt):
    """Parameters and AdamW state created SHARDED, never whole on a device."""
    import jax

    from perfbench import program

    _, param_sh, _, opt_sh = abstract_state(config, cfg, mesh, opt)
    params = program.init_weights(config, cfg, seed, out_shardings=param_sh)
    opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
    jax.block_until_ready(opt_state)
    return params, opt_state, param_sh, opt_sh


def make_step(config, cfg, opt, mesh):
    import jax
    import optax

    from perfbench.manifest import resolve

    loss_fn = resolve(config["program"]["loss"])
    # XLA cannot partition a Mosaic kernel: under a mesh the flash kernel
    # runs per device, on that device's batch rows and heads
    attn = resolve(config["program"]["attention"])(mesh)

    def perfbench_train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(
            p, {"tokens": tokens}, cfg, attn_impl=attn))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return perfbench_train_step


def compile_step(config, cfg, opt, mesh, params, opt_state, tokens,
                 param_sh, opt_sh):
    import jax

    lowered = jax.jit(make_step(config, cfg, opt, mesh),
                      donate_argnums=(0, 1),
                      out_shardings=(param_sh, opt_sh, None)).lower(
        params, opt_state, tokens)
    return lowered.compile(), "tpu_custom_call" in lowered.as_text()


def seeded_batch(seed: int, batch: int, seq: int, vocab: int):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq), dtype=np.int32)


def reference_check(config, shape, mix, mesh, seed, opt):
    """The sharded step's first loss at the cell's widths and depth 2
    against the plain reference's loss on the same tokens."""
    import jax

    from ray_tpu.parallel.mesh import batch_sharding

    from perfbench import program
    from perfbench.manifest import resolve

    shape2 = {**shape, "num_hidden_layers": 2}
    cfg2 = program.model_config(config, shape2)
    params, opt_state, param_sh, opt_sh = _state(config, cfg2, mesh, seed, opt)
    host_tokens = seeded_batch(seed, mix["batch"], mix["seq_len"],
                               shape["vocab_size"])
    one = mesh.devices.flat[0]
    ref_loss = float(resolve(config["program"]["reference_loss"])(
        resolve(config["program"]["reference_weights"])(
            jax.device_put(params, one)),
        jax.device_put(host_tokens, one), shape2))
    tokens = jax.device_put(host_tokens, batch_sharding(mesh))
    step, _ = compile_step(config, cfg2, opt, mesh, params, opt_state, tokens,
                           param_sh, opt_sh)
    params, opt_state, loss = step(params, opt_state, tokens)
    loss = float(loss)
    del params, opt_state
    return {"step_loss": loss, "reference_loss": ref_loss,
            "ok": abs(loss - ref_loss) <= LOSS_TOL}


def train_loop(c):
    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshSpec, batch_sharding, make_mesh

    from perfbench import program, train_spans, xplane

    compiles = program.CompileCounter()
    config, mix, rehearse = c["config"], c["mix"], c["rehearse"]
    shape = program.shape_of(config, rehearse)
    devices = jax.devices()
    if len(devices) != c["chips"] or (
            not rehearse and devices[0].platform != "tpu"):
        train.report({"no_chip": f"jax found {devices}"})
        return
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices)
    opt = optax.adamw(**config["optimizer"]["adamw"])
    t0 = time.perf_counter()
    ref = reference_check(config, shape, mix, mesh, c["seed"], opt)
    reference_s = time.perf_counter() - t0

    cfg = program.model_config(config, shape)
    t0 = time.perf_counter()
    params, opt_state, param_sh, opt_sh = _state(config, cfg, mesh, c["seed"],
                                                 opt)
    batch, seq = mix["batch"], mix["seq_len"]
    tokens = jax.device_put(
        seeded_batch(c["seed"], batch, seq, shape["vocab_size"]),
        batch_sharding(mesh))
    jax.block_until_ready(tokens)
    weights_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    step, has_kernel = compile_step(config, cfg, opt, mesh, params, opt_state,
                                    tokens, param_sh, opt_sh)
    losses = []
    for _ in range(2):   # warm-up: the window opens on a step already run
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    programs_s = time.perf_counter() - t0

    trace_dir = os.path.join(c["root"], "chiprun_out", "perfbench_trace")
    trace_at = max(1, int(c["seconds"] // 3)) if c["trace"] else None
    traced, window_ns, k = None, None, 0
    c0 = compiles.count
    t_open_wall, t_open = time.time(), time.perf_counter()
    closes = []
    while time.perf_counter() - t_open < c["seconds"]:
        if c["trace"] and k == trace_at:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_ns = [time.perf_counter_ns(), None]
        tracing = window_ns is not None and window_ns[1] is None
        with (jax.profiler.TraceAnnotation(f"perfbench/train.step#{k}")
              if tracing else contextlib.nullcontext()):
            params, opt_state, loss = step(params, opt_state, tokens)
            jax.block_until_ready((params, opt_state, loss))
        closes.append(time.perf_counter())
        losses.append(float(loss))
        k += 1
        if tracing and k == trace_at + c["trace_steps"]:
            window_ns[1] = time.perf_counter_ns()
            jax.profiler.stop_trace()
    in_window = compiles.count - c0
    if window_ns is not None and window_ns[1] is None:
        window_ns[1] = time.perf_counter_ns()
        jax.profiler.stop_trace()
    if window_ns is not None:
        trace = xplane.load(xplane.find_xplane(trace_dir))
        if c.get("sample_to"):
            xplane.write_sample(trace, c["sample_to"])
        traced = train_spans.summarise(xplane.reduce(trace),
                                       config["programs"]["train_step"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    train.report({
        "losses": losses, "window_losses": losses[2:],
        "steps": len(closes), "window_s": closes[-1] - t_open,
        "tokens_per_step": batch * seq, "t_open_wall": t_open_wall,
        "weights_s": weights_s, "programs_s": programs_s,
        "reference_s": reference_s, "reference": ref,
        "has_tpu_custom_call": has_kernel, "compiles_in_window": in_window,
        "trace": traced,
        "trace_window_s": None if window_ns is None
        else (window_ns[1] - window_ns[0]) / 1e9,
        "params": cfg.param_count(),
        "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                         for d in devices],
        "peak_bytes_in_use": [(d.memory_stats() or {}).get(
            "peak_bytes_in_use") for d in devices],
    })
