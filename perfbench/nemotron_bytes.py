"""Bytes a decode step of the hybrid Mamba-2 / attention / routed-expert
family NEEDS, from shapes. Counts, not timings (``flops.py`` counts a dense
decoder; this file the family of ``nemotron-3-nano-30b-a3b-serve1``).

``shape`` is the configuration file's published keys. Weights and pages are
bfloat16 (2 bytes), the router, the scan's per-head vectors and the SSM state
float32 (4 bytes), as the program holds them.
"""

from __future__ import annotations


def _counts(shape: dict):
    p = shape["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def mamba_layer_bytes(shape: dict) -> int:
    d = shape["hidden_size"]
    h = shape["mamba_num_heads"]
    di = h * shape["mamba_head_dim"]
    conv_dim = di + 2 * shape["n_groups"] * shape["ssm_state_size"]
    bf16 = (d * (di + conv_dim + h) + conv_dim * (shape["conv_kernel"] + 1)
            + di + di * d + d)
    return 2 * bf16 + 4 * 3 * h


def attention_layer_bytes(shape: dict) -> int:
    d, hd = shape["hidden_size"], shape["head_dim"]
    q = shape["num_attention_heads"] * hd
    kv = shape["num_key_value_heads"] * hd
    return 2 * (2 * d * q + 2 * d * kv + d)


def expert_bytes(shape: dict) -> int:
    """One routed expert: its up and down matrices."""
    return 2 * 2 * shape["hidden_size"] * shape["moe_intermediate_size"]


def expert_layer_fixed_bytes(shape: dict) -> int:
    """What every step reads of an expert layer whatever the routing: the
    router and its bias (float32), the shared expert, the norm."""
    d = shape["hidden_size"]
    return (4 * (d + 1) * shape["router_width"]
            + 2 * (2 * d * shape["moe_shared_expert_intermediate_size"] + d))


def slot_state_bytes(shape: dict) -> int:
    """One slot's recurrent state: the SSM state (float32) and the
    convolution tail (bfloat16) of every Mamba layer."""
    n_m, _, _ = _counts(shape)
    h = shape["mamba_num_heads"]
    di = h * shape["mamba_head_dim"]
    conv_dim = di + 2 * shape["n_groups"] * shape["ssm_state_size"]
    return n_m * (4 * di * shape["ssm_state_size"]
                  + 2 * (shape["conv_kernel"] - 1) * conv_dim)


def kv_bytes_per_position(shape: dict) -> int:
    _, n_a, _ = _counts(shape)
    return n_a * 2 * shape["num_key_value_heads"] * shape["head_dim"] * 2


def weight_bytes(shape: dict) -> int:
    """All the weights this chip holds."""
    n_m, n_a, n_e = _counts(shape)
    d = shape["hidden_size"]
    return (n_m * mamba_layer_bytes(shape) + n_a * attention_layer_bytes(shape)
            + n_e * (expert_layer_fixed_bytes(shape)
                     + shape["n_routed_experts"] * expert_bytes(shape))
            + 2 * (2 * shape["vocab_size"] * d + d))


def decode_min_bytes(shape: dict, live_positions: float, slots: float,
                     experts_hit: float) -> float:
    """The least bytes one decode step must move: every weight outside the
    routed experts once (Mamba, attention, routers, shared experts, norms,
    the head), the matrices of the held experts that got a token
    (``experts_hit``: summed over the expert layers), the recurrent state of
    the active slots read AND written, the live K and V of the active
    sequences (``live_positions`` = the sum of their lengths) and the
    embedding rows of the slots' tokens. K/V rows written and activations
    are left out: thousands of times smaller."""
    n_m, n_a, n_e = _counts(shape)
    d = shape["hidden_size"]
    fixed = (n_m * mamba_layer_bytes(shape)
             + n_a * attention_layer_bytes(shape)
             + n_e * expert_layer_fixed_bytes(shape)
             + 2 * (shape["vocab_size"] * d + d))
    return float(fixed + experts_hit * expert_bytes(shape)
                 + 2 * slots * slot_state_bytes(shape)
                 + live_positions * kv_bytes_per_position(shape)
                 + 2 * slots * d)


def experts_hit_per_step(ctx: dict) -> list:
    """``experts_hit`` (held experts that got a token, summed over the
    expert layers) of the window's steps that decoded, from the program's
    ``serve.engine.step`` rows; nothing where the program writes none."""
    from perfbench import program_spans as ps

    return [f["experts_hit"] for f in ps.in_window(ctx, ps.STEP)
            if f.get("active") and "experts_hit" in f]
