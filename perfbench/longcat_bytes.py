"""Bytes and operations a decode step of the latent-attention / zero-expert
family NEEDS, from shapes and the step's own counters. Counts, not timings
(``flops.py`` counts a dense decoder, ``nemotron_bytes.py`` the hybrid family,
``sala_bytes.py`` the lightning / block-sparse one; this file the family of
``longcat-flash-omni-serve1``).

``shape`` is the configuration file's published keys. Weights and latent
pages are bfloat16 (2 bytes), the router and its bias float32 (4), as the
program holds them. The counts follow from shapes alone, so they are the
same whatever implements a layer.
"""

from __future__ import annotations


def attention_bytes(shape: dict) -> int:
    """One latent-attention sublayer's weights."""
    d, H = shape["hidden_size"], shape["num_attention_heads"]
    q, c = shape["q_lora_rank"], shape["kv_lora_rank"]
    dn, dr, dv = (shape["qk_nope_head_dim"], shape["qk_rope_head_dim"],
                  shape["v_head_dim"])
    return 2 * (d * q + q + q * H * (dn + dr) + d * (c + dr) + c
                + c * H * (dn + dv) + H * dv * d + d)


def mlp_bytes(shape: dict) -> int:
    d = shape["hidden_size"]
    return 2 * (3 * d * shape["ffn_hidden_size"] + d)


def expert_bytes(shape: dict) -> int:
    """One held expert's three matrices."""
    return 2 * 3 * shape["hidden_size"] * shape["expert_ffn_hidden_size"]


def router_bytes(shape: dict) -> int:
    return 4 * (shape["hidden_size"] + 1) * shape["router_width"]


def layer_bytes(shape: dict) -> int:
    """One double layer as this chip holds it."""
    return (2 * attention_bytes(shape) + 2 * mlp_bytes(shape)
            + router_bytes(shape)
            + shape["n_routed_experts"] * expert_bytes(shape))


def weight_bytes(shape: dict, embedding: bool = True) -> int:
    """The weights this chip holds; a decode step reads all but the
    embedding table (of which it reads one row a slot)."""
    d = shape["hidden_size"]
    return (shape["num_layers"] * layer_bytes(shape)
            + 2 * (shape["vocab_size"] * d * (2 if embedding else 1) + d))


def latent_row_bytes(shape: dict) -> int:
    """One position's cache row of one sublayer."""
    return 2 * (shape["kv_lora_rank"] + shape["qk_rope_head_dim"])


def decode_min_bytes(shape: dict, latent_positions: float, slots: float
                     ) -> float:
    """The least bytes one decode step must move: every weight but the
    embedding table once (all held experts: the count is from shapes, not
    from which experts a step hit), the cached rows of the active slots read
    once a sublayer (``latent_positions``: the step's own counter), one row
    written a slot a sublayer, and the embedding rows of the slots' tokens."""
    sub = 2 * shape["num_layers"]
    return float(weight_bytes(shape, embedding=False)
                 + sub * latent_positions * latent_row_bytes(shape)
                 + slots * (sub * latent_row_bytes(shape)
                            + 2 * shape["hidden_size"]))


def latent_attn_flops(shape: dict, latent_positions: float) -> float:
    """Operations of the absorbed attention over the cached rows, all
    sublayers: per head a position's row is contracted once for the score
    (C + dr) and once for the weighted sum of latents (C)."""
    c, dr = shape["kv_lora_rank"], shape["qk_rope_head_dim"]
    return (2.0 * shape["num_attention_heads"] * (2 * c + dr)
            * latent_positions * 2 * shape["num_layers"])


def latent_steps(ctx: dict) -> list:
    """The window's ``serve.engine.step`` rows on which a decode step landed
    and that carry this family's counters; nothing where the program writes
    none."""
    from perfbench import program_spans as ps

    return [f for f in ps.in_window(ctx, ps.STEP)
            if f.get("landed") and "latent_positions" in f]


def per_step(ctx: dict, field: str):
    """The mean of one counter over the decode steps that landed in the
    window (a row sums the steps its call landed, ``landed`` counts them)."""
    rows = latent_steps(ctx)
    steps = sum(f["landed"] for f in rows)
    return sum(f[field] for f in rows) / steps if steps else None
