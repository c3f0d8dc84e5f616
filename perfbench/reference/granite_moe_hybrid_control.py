"""The controls of ``granite_moe_hybrid_check``: the same comparison with the
engine one precision step under what the configuration states. The benchmark's
runs never run it; the check's limits were set from it, and
``tests/perfbench/test_granite_check.py`` keeps it at a toy width.

    python3 perfbench/reference/granite_moe_hybrid_control.py \\
        --config <name> --seeds a,b,c [--state bfloat16]

On the chip, in one process and with no cluster: per seed the sound engine,
then the engine on its own ``ops.quant`` int8 weights (every projection of
the Mamba and attention mixers, of the shared expert and of the held experts,
each expert with scales of its own, through int8 and back into the served
type; the embedding, which is also the tied head, the convolution, the
per-head vectors, the router and the norms stay), each against the reference
over the weights as the seed made them. The int8 engine has to come out NOT
correct on every seed.

``--state bfloat16`` adds the second control: the sound weights with every
slot's SSM state rounded to bfloat16 after each decode step and after the
admission's write (what a bfloat16 state cache would hold; the recurrence's
arithmetic stays float32), to say whether the check sees the state's dtype.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import REF_PROMPT  # noqa: E402
from perfbench.reference import nemotron_h_control as base  # noqa: E402
from perfbench.reference.dense_control import _generate  # noqa: E402

#: what a weight-only int8 path of this family would quantise
QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate", "w_up",
              "w_down")


def round_weights_in_place(params: dict):
    """``nemotron_h_control``'s walk (one leaf at a time through the
    program's own quantiser and back, stacked experts one expert at a time)
    over this family's keys."""
    keys, base.QUANT_KEYS = base.QUANT_KEYS, QUANT_KEYS
    try:
        base.round_weights_in_place(params)
    finally:
        base.QUANT_KEYS = keys


def round_state_after_every_step(engine):
    """Make ``engine`` hold what a bfloat16 SSM state cache would: after
    every step and every admission's state write, each slot's SSM state is
    rounded to bfloat16 (and kept in the float32 arrays the programs take)."""
    import jax
    import jax.numpy as jnp

    rounded = jax.jit(lambda ssm: [s.astype(jnp.bfloat16).astype(s.dtype)
                                   for s in ssm], donate_argnums=(0,))
    step, admit = engine.step, engine._admit

    def step_then_round():
        events = step()
        engine.ssm = rounded(engine.ssm)
        return events

    def admit_then_round():
        admit()
        engine.ssm = rounded(engine.ssm)

    engine.step, engine._admit = step_then_round, admit_then_round


def one_seed(config: dict, seed: int, rehearse: bool, state: str = "float32"
             ) -> dict:
    """``{"sound" | "w8" | "state16": the check's return}`` for one seed."""
    from ray_tpu.models.paged import PagedEngine

    from perfbench import program, traffic as tg
    from perfbench.reference import granite_moe_hybrid_check as chk

    gc.collect()    # the seed before's engine (13.6 of the chip's 15.75 GB)
    shape = program.shape_of(config, rehearse)
    cfg = program.model_config(config, shape)
    kw = {k: v for k, v in program.section(config, "engine", rehearse).items()
          if k != "kv_cache"}
    prompt = tg.prompt_tokens(seed, 10**6 + 99, REF_PROMPT,
                              shape["vocab_size"])
    params = program.init_weights(config, cfg, seed)
    engine = PagedEngine(params, cfg, **kw)
    out = {"sound": chk.check(engine, prompt, _generate(engine, prompt),
                              config, shape)}
    if state == "bfloat16":
        round_state_after_every_step(engine)
        out["state16"] = chk.check(engine, prompt, _generate(engine, prompt),
                                   config, shape)
        del engine.step, engine._admit      # the class's own again
    round_weights_in_place(params)          # the engine holds this tree
    emitted = _generate(engine, prompt)
    got = chk.program_out(engine, prompt, emitted, config, shape)
    engine.params = None
    del params
    params = program.init_weights(config, cfg, seed)
    out["w8"] = chk.compare(got, prompt, emitted, params, config, shape)
    return out


def main(argv=None):
    from perfbench.manifest import Manifest
    from perfbench.runners.common import make_room_in_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--state", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    make_room_in_compile_cache()
    from ray_tpu._private import jax_platform

    jax_platform.install_hook()      # the checkout's persistent compile cache
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("perfbench: the control needs the chip "
                         "(--rehearse runs it at toy widths on the CPU)")
    config = Manifest(ROOT).config(args.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        for path, r in one_seed(config, seed, args.rehearse,
                                args.state).items():
            sigma = {"probe": r["notes"]["probe_logit_std"]}
            print(json.dumps({
                "seed": seed, "path": path, "ok": r["ok"],
                "sigma": r["notes"]["ref_logit_std"],
                **{x["name"] + ("" if x["limit"] == 0 else "_sigma"):
                   x["value"] / (1.0 if x["limit"] == 0 else sigma.get(
                       x["name"].split("_")[0], r["notes"]["ref_logit_std"]))
                   for x in r["readings"]},
                **{k: r["notes"][k] for k in (
                    "routing_decisions", "routing_disagreements",
                    "routing_worst_under", "exact_argmax",
                    "probe_exact_argmax", "reference_s",
                    "probe_reference_s")}}), flush=True)


if __name__ == "__main__":
    main()
