"""The control of ``deepseek_v3_check``: the same comparison with the engine
one precision step under the bfloat16 the configuration states, which has to
come out NOT correct on every seed, by the admission's row (a), the steps'
rows (b) and the MTP block's rows (c). The benchmark's runs never run it; the
check's limits were set from it, and ``tests/perfbench/test_gigachat_check.py``
keeps it at a toy width.

    python3 perfbench/reference/deepseek_v3_control.py --config <name> --seeds a,b,c [--sound-only]

On the chip, in one process and with no cluster: per seed the sound engine,
then (unless ``--sound-only``) the engine on its own ``ops.quant`` int8
weights (every projection of the latent attention, the dense MLP, the shared
and the held experts each with scales of their own, ``eh_proj`` and the head,
through int8 and back into the served type; the embedding, the routers and
the norms stay), each against the reference over the weights as the seed made
them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import REF_NEW, REF_PROMPT  # noqa: E402

#: what a weight-only int8 path of this family would quantise
QUANT_KEYS = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo", "w_gate", "w_up",
              "w_down", "eh_proj", "lm_head")


def round_weights_in_place(params: dict, keys=QUANT_KEYS):
    """Every leaf named in ``keys`` through the program's own quantiser
    (``ops.quant.quantize_array``: int8, one scale per output channel;
    stacked experts one expert at a time) and back, one leaf at a time: no
    second model in memory. (``nemotron_h_control``'s walk, which reads its
    keys from its own module.)"""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import quant

    def through(w):
        q = quant.quantize_array(w)
        return (q.w.astype(jnp.float32)
                * q.s.astype(jnp.float32)).astype(w.dtype)

    flat = jax.jit(through)
    stacked = jax.jit(jax.vmap(through))

    def visit(node):
        for key, leaf in list(node.items()):
            if isinstance(leaf, dict):
                visit(leaf)
            elif isinstance(leaf, list):
                for item in leaf:
                    visit(item)
            elif key in keys:
                node[key] = (stacked if leaf.ndim == 3 else flat)(leaf)
                del leaf

    visit(params)
    jax.block_until_ready(params)


def _generate(engine, prompt, defaults: dict):
    """The contract's request as the runner sends it: nothing said of
    sampling, so the deployment's default applies."""
    engine.submit("control", prompt, max_new_tokens=REF_NEW, **defaults)
    return engine.run_to_completion()["control"]


def one_seed(config: dict, seed: int, rehearse: bool,
             sound_only: bool = False) -> dict:
    """``{"sound" | "w8": the check's return}`` for one seed."""
    from ray_tpu.models.paged import PagedEngine

    from perfbench import program, traffic as tg
    from perfbench.reference import deepseek_v3_check as chk

    shape = program.shape_of(config, rehearse)
    cfg = program.model_config(config, shape)
    kw = {k: v for k, v in program.section(config, "engine", rehearse).items()
          if k != "kv_cache"}
    defaults = kw.pop("generation_defaults", {})
    prompt = tg.prompt_tokens(seed, 10**6 + 99, REF_PROMPT,
                              shape["vocab_size"])
    params = program.init_weights(config, cfg, seed)
    engine = PagedEngine(params, cfg, **kw)
    out = {"sound": chk.check(engine, prompt,
                              _generate(engine, prompt, defaults), config,
                              shape)}
    if sound_only:
        return out
    round_weights_in_place(params)          # the engine holds this tree
    emitted = _generate(engine, prompt, defaults)
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
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sound-only", action="store_true")
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
                                args.sound_only).items():
            sigma = {True: r["notes"]["probe_logit_std"],
                     False: r["notes"]["ref_logit_std"]}
            print(json.dumps({
                "seed": seed, "path": path, "ok": r["ok"],
                "sigma": r["notes"]["ref_logit_std"],
                **{x["name"] + ("" if x["limit"] == 0 else "_sigma"):
                   x["value"] / (1.0 if x["limit"] == 0 else sigma[
                       x["name"].startswith("probe_")])
                   for x in r["readings"]},
                **{k: r["notes"][k] for k in (
                    "steps", "drafts_accepted", "step_rows_compared",
                    "mtp_rows_compared", "routing_decisions",
                    "routing_disagreements", "routing_worst_under",
                    "reference_s", "probe_reference_s")}}), flush=True)


if __name__ == "__main__":
    main()
