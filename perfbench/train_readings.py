"""The loss of the train cell's own step, seed by seed, in one process: what
the limits of ``runners/train.window_moved`` are set from. The benchmark's
runs never run it. One compile (or one load from the cache), then per seed
the state as the cell makes it, the cell's batch and ``--steps`` steps of
the cell's compiled program; a line of JSON a seed with every loss and the
readings the runner would compare.

    python3 perfbench/train_readings.py --cell train-fsdp2-tp2 \\
        --seeds 3197000303:3,3301000019 --steps 27

``seed:n`` runs only n steps of that seed (to hold a known run's first
losses against this script's). On the chips the cell asks for; ``--rehearse``
runs toy widths on as many virtual CPU devices.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from perfbench.manifest import Manifest
    from perfbench.runners.common import make_room_in_compile_cache
    from perfbench.runners.train import window_moved

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=27)
    ap.add_argument("--warm-up", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    man = Manifest(ROOT)
    cell = man.cell(args.cell)
    config, mix = man.config(cell["config"]), man.traffic(cell["traffic"])
    if args.rehearse:
        mix = {**mix, **mix.get("rehearsal", {})}
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()
    make_room_in_compile_cache()
    from ray_tpu._private import jax_platform

    jax_platform.install_hook()      # the checkout's persistent compile cache
    import jax
    import optax

    from ray_tpu.parallel.mesh import MeshSpec, batch_sharding, make_mesh

    from perfbench import program, trainloop

    devices = jax.devices()
    if len(devices) != cell["chips"] or (
            not args.rehearse and devices[0].platform != "tpu"):
        raise SystemExit(f"perfbench: the readings need the cell's "
                         f"{cell['chips']} chips, jax found {devices}")
    shape = program.shape_of(config, args.rehearse)
    cfg = program.model_config(config, shape)
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices)
    opt = optax.adamw(**config["optimizer"]["adamw"])
    step = None
    for item in args.seeds.split(","):
        seed, _, n = item.partition(":")
        seed, n = int(seed), int(n or args.steps)
        params, opt_state, param_sh, opt_sh = trainloop._state(
            config, cfg, mesh, seed, opt)
        tokens = jax.device_put(
            trainloop.seeded_batch(seed, mix["batch"], mix["seq_len"],
                                   shape["vocab_size"]),
            batch_sharding(mesh))
        if step is None:
            step, _ = trainloop.compile_step(config, cfg, opt, mesh, params,
                                             opt_state, tokens, param_sh,
                                             opt_sh)
        losses = []
        for _ in range(n):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
        del params, opt_state, tokens     # room for the next seed's state
        window = losses[args.warm_up:]
        print(json.dumps({
            "seed": seed, "steps": n, "losses": losses,
            "lowest_over_seeded": min(window) / losses[0] if window else None,
            "last_over_seeded": window[-1] / losses[0] if window else None,
            "compared": [[text, ok] for text, ok in
                         window_moved(losses, window)]}), flush=True)


if __name__ == "__main__":
    main()
