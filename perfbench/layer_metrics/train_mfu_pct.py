"""Model FLOPs per step (``perfbench.flops.train_flops_per_step``: matmuls of
forward and backward, causal attention, no recompute, no embedding lookup)
over the step's device time x chips x the chip's bf16 peak."""

from perfbench import flops


def read(ctx):
    t = ctx.get("trace") or {}
    times = [s for s in t.get("step_device_s") or [] if s]
    if not times or not ctx.get("peaks"):
        return None
    need = flops.train_flops_per_step(ctx["shape"], ctx["mix"]["batch"],
                                      ctx["mix"]["seq_len"])
    return flops.mfu_pct(need, max(times), ctx["cell"]["chips"],
                         ctx["peaks"]["bf16_flops_per_s"])
