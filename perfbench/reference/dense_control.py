"""The control of ``dense_check``: the same comparison with the engine one
precision step under the bfloat16 the configuration states, which has to
come out NOT correct. The benchmark's runs never run it; its limits were set
from it, and ``tests/perfbench/test_dense_check.py`` keeps it at a toy width.

    python3 perfbench/reference/dense_control.py --config <name> --seeds a,b,c

On the chip, in one process and with no cluster: per seed the sound engine,
then two lower-precision paths of the program's own, each against the
reference over the weights as the seed made them:

- ``w8``: the engine computes with ``ops.quant.quantize_params``' weights
  (int8, one scale per output channel, the projections and the head; the
  embedding and the norms stay), dequantised into the served type;
- ``kv8``: the engine keeps its pages in int8 (``kv_dtype="int8"``). The
  prefill program does not read pages, so only ``max_margin`` can see it.
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


def round_weights_in_place(params: dict):
    """Every leaf the program's own int8 path would quantise (its list,
    ``ops.quant._QUANT_KEYS``, and its quantiser), through int8 and back,
    one leaf at a time: no second model in memory."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import quant

    @jax.jit
    def through_int8(w):
        q = quant.quantize_array(w)
        return (q.w.astype(jnp.float32)
                * q.s.astype(jnp.float32)).astype(w.dtype)

    def visit(node):
        for key, leaf in list(node.items()):
            if isinstance(leaf, dict):
                visit(leaf)
            elif isinstance(leaf, list):
                for item in leaf:
                    visit(item)
            elif key in quant._QUANT_KEYS:
                node[key] = through_int8(leaf)
                del leaf

    visit(params)
    jax.block_until_ready(params)


def _generate(engine, prompt):
    engine.submit("control", prompt, max_new_tokens=REF_NEW)
    return engine.run_to_completion()["control"]


def one_seed(config: dict, seed: int, rehearse: bool) -> dict:
    """``{"sound" | "w8" | "kv8": the check's return}`` for one seed."""
    from ray_tpu.models.paged import PagedEngine

    from perfbench import program, traffic as tg
    from perfbench.reference import dense_check

    shape = program.shape_of(config, rehearse)
    cfg = program.model_config(config, shape)
    kw = {k: v for k, v in program.section(config, "engine", rehearse).items()
          if k != "kv_cache"}
    prompt = tg.prompt_tokens(seed, 10**6 + 99, REF_PROMPT,
                              shape["vocab_size"])
    params = program.init_weights(config, cfg, seed)
    engine = PagedEngine(params, cfg, **kw)
    out = {"sound": dense_check.check(engine, prompt,
                                      _generate(engine, prompt), config, shape)}

    round_weights_in_place(params)          # the engine holds this tree
    emitted = _generate(engine, prompt)
    row = dense_check.prefill_row(engine, prompt)
    engine.params = None
    del params
    params = program.init_weights(config, cfg, seed)
    out["w8"] = dense_check.compare(row, prompt, emitted, params, config, shape)

    del engine
    engine = PagedEngine(params, cfg, **{**kw, "kv_dtype": "int8"})
    out["kv8"] = dense_check.check(engine, prompt, _generate(engine, prompt),
                                   config, shape)
    return out


def main(argv=None):
    from perfbench.manifest import Manifest
    from perfbench.runners.common import make_room_in_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
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
        for path, r in one_seed(config, seed, args.rehearse).items():
            sigma = r["notes"]["ref_logit_std"]
            print(json.dumps({
                "seed": seed, "path": path, "ok": r["ok"], "sigma": sigma,
                **{x["name"] + "_sigma": x["value"] / sigma
                   for x in r["readings"]},
                "exact_argmax": r["notes"]["exact_argmax"]}), flush=True)


if __name__ == "__main__":
    main()
