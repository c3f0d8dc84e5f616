"""The share of the decode program's device time that the absorbed
attention's arithmetic would need at the chip's bf16 peak: ``2 x heads x
(2 x kv_lora_rank + qk_rope_head_dim)`` operations a cached position a
sublayer (``perfbench.longcat_bytes.latent_attn_flops``, positions from the
step rows' ``latent_positions``) over the peak, over the decode program's
device time. A cache whose read costs arithmetic: ~120 operations a byte."""

from perfbench import longcat_bytes as lb, serve_spans


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    positions = lb.per_step(ctx, "latent_positions")
    if device_s is None or positions is None or not ctx.get("peaks"):
        return None
    need_s = lb.latent_attn_flops(ctx["shape"], positions) \
        / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * need_s / device_s
