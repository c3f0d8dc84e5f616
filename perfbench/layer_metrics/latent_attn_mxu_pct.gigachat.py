"""The share of the step program's device time that the absorbed attention's
arithmetic would need at the chip's bf16 peak, with TWO query rows a slot:
``2 x rows x heads x (2 x kv_lora_rank + qk_rope_head_dim)`` operations a
LIVE cached position a layer (``perfbench.gigachat_bytes.latent_attn_flops``,
positions from the step rows' ``latent_positions``) over the peak, over the
step program's device time: the two-row form's share of its roofline."""

from perfbench import gigachat_bytes as gb, serve_spans


def read(ctx):
    device_s = serve_spans.decode_device_s(ctx)
    positions = gb.per_step(ctx, "latent_positions")
    if device_s is None or positions is None or not ctx.get("peaks"):
        return None
    need_s = gb.latent_attn_flops(ctx["shape"], positions) \
        / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * need_s / device_s
