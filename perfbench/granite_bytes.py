"""Bytes a decode step of the Granite-MoE-hybrid family (Mamba-2 layers nine
to one attention layer, a routed expert layer beside a shared expert behind
every mixer) NEEDS, from shapes and the step's own counters. Counts, not
timings (``flops.py`` counts a dense decoder, ``nemotron_bytes.py`` the older
Mamba-2 family, ``lfm2_bytes.py`` the gated-short-convolution one; this file
the family of ``granite-4.0-h-small-serve1``).

``shape`` is the configuration file's published keys. Weights, pages and
convolution tails are bfloat16 (2 bytes); the router, the scan's per-head
vectors and the SSM state float32 (4), as the program holds them. The counts
follow from shapes alone, so they are the same whatever implements a layer.
The experts are counted BY WHAT A STEP HIT (``experts_hit``, the step's own
counter), not by the 36 held: an expert no token picked need not be read
(PR 54's refusal, ``longcat_decode_hbm_roofline_pct`` 112.6 %, is the lesson).
"""

from __future__ import annotations

# the mean of a counter over the window's landed decode steps: the same rows
# of ``serve.engine.step`` as the other held-experts family's
from perfbench.lfm2_bytes import per_step  # noqa: F401 (the readers' too)


def kinds(shape: dict) -> list:
    """The type of each layer held: the first of the published list."""
    return list(shape["layer_types"])[:shape["num_hidden_layers"]]


def head_dim(shape: dict) -> int:
    return int(shape.get("head_dim")
               or shape["hidden_size"] // shape["num_attention_heads"])


def _mamba_sizes(shape: dict):
    h = shape["mamba_n_heads"]
    di = h * shape["mamba_d_head"]
    return h, di, di + 2 * shape["mamba_n_groups"] * shape["mamba_d_state"]


def mamba_bytes(shape: dict) -> int:
    """One Mamba-2 mixer: ``W_in``, the convolution and its bias, the gated
    norm, ``W_out`` (bfloat16); ``dt_bias``, ``A_log``, ``D`` (float32)."""
    d = shape["hidden_size"]
    h, di, conv_dim = _mamba_sizes(shape)
    return (2 * (d * (di + conv_dim + h)
                 + conv_dim * (shape["mamba_d_conv"] + 1) + di + di * d)
            + 4 * 3 * h)


def attention_bytes(shape: dict) -> int:
    d, hd = shape["hidden_size"], head_dim(shape)
    return 2 * (2 * d * shape["num_attention_heads"] * hd
                + 2 * d * shape["num_key_value_heads"] * hd)


def expert_bytes(shape: dict) -> int:
    """One routed expert's three matrices."""
    return 2 * 3 * shape["hidden_size"] * shape["intermediate_size"]


def ffn_fixed_bytes(shape: dict) -> int:
    """What every step reads of a layer's expert half whatever it routes:
    the router (float32) and the shared expert."""
    d = shape["hidden_size"]
    return (4 * d * shape["router_width"]
            + 2 * 3 * d * shape["shared_intermediate_size"])


def outside_experts_bytes(shape: dict) -> int:
    """Every weight a step reads whatever it routes: the mixers, the routers,
    the shared experts, every norm, and the embedding table ONCE, as the
    tied head."""
    d, types = shape["hidden_size"], kinds(shape)
    return (types.count("mamba") * mamba_bytes(shape)
            + types.count("attention") * attention_bytes(shape)
            + len(types) * ffn_fixed_bytes(shape)
            + 2 * (2 * d * len(types) + d)
            + 2 * shape["vocab_size"] * d)


def weight_bytes(shape: dict) -> int:
    """The weights this chip holds (``num_local_experts``: those held)."""
    return (outside_experts_bytes(shape)
            + len(kinds(shape)) * shape["num_local_experts"]
            * expert_bytes(shape))


def kv_row_bytes(shape: dict) -> int:
    """One position's K and V of one attention layer."""
    return 2 * 2 * shape["num_key_value_heads"] * head_dim(shape)


def slot_state_bytes(shape: dict) -> int:
    """One slot's recurrent state: the SSM state (float32) and the
    convolution tail (bfloat16) of every Mamba layer held."""
    _, di, conv_dim = _mamba_sizes(shape)
    return kinds(shape).count("mamba") * (
        4 * di * shape["mamba_d_state"]
        + 2 * (shape["mamba_d_conv"] - 1) * conv_dim)


def decode_min_bytes(shape: dict, experts_hit: float,
                     context_positions: float, slots: float) -> float:
    """The least bytes one decode step must move: every weight outside the
    routed experts (the tied table once, as the head), the experts that got
    a token (``experts_hit``: summed over the layers, the step's own
    counter), the recurrent state of the active slots read AND written
    (float32 SSM state, bfloat16 tails), the keys and values each active
    slot's query attends in every attention layer (``context_positions`` a
    layer: the step's own counter) with one K/V row written a slot, and the
    embedding rows of the slots' tokens."""
    pooled = kinds(shape).count("attention")
    return float(outside_experts_bytes(shape)
                 + experts_hit * expert_bytes(shape)
                 + 2 * slots * slot_state_bytes(shape)
                 + kv_row_bytes(shape) * pooled * (context_positions + slots)
                 + 2 * slots * shape["hidden_size"])


def step_min_bytes(ctx: dict):
    """``decode_min_bytes`` of the window's mean decode step, from its own
    counters; nothing where the program writes none."""
    hit = per_step(ctx, "experts_hit")
    if hit is None:
        return None
    return decode_min_bytes(ctx["shape"], hit,
                            per_step(ctx, "context_positions"),
                            per_step(ctx, "moe_rows"))
