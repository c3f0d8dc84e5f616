"""Bytes and operations a decode step of the DeepSeek-V3 family NEEDS, from
shapes and the step's own counters. Counts, not timings (``longcat_bytes.py``
counts the other latent-attention family; this file the family of
``gigachat3.1-702b-a36b-serve1``).

``shape`` is the configuration file's published keys. Weights and latent
pages are bfloat16 (2 bytes), the routers and their biases float32 (4), as the
program holds them. A step takes TWO rows a slot (the last committed token
and its draft) through the decoder's layers and the MTP block. The counts
follow from shapes alone, so they are the same whatever implements a layer.
PR 54's refused reading (a share of 112.6 %) is why the experts are counted
as the step HIT them and the latent rows as they are LIVE, never as held.
"""

from __future__ import annotations

ROWS = 2    # query rows a slot a step: the committed token and its draft


def attention_bytes(shape: dict) -> int:
    """One latent-attention layer's weights, its two norms' among them."""
    d, H = shape["hidden_size"], shape["num_attention_heads"]
    q, c = shape["q_lora_rank"], shape["kv_lora_rank"]
    dn, dr, dv = (shape["qk_nope_head_dim"], shape["qk_rope_head_dim"],
                  shape["v_head_dim"])
    return 2 * (d * q + q + q * H * (dn + dr) + d * (c + dr) + c
                + c * H * (dn + dv) + H * dv * d + 2 * d)


def mlp_bytes(shape: dict) -> int:
    return 2 * 3 * shape["hidden_size"] * shape["intermediate_size"]


def expert_bytes(shape: dict) -> int:
    """One expert's three matrices (a routed one's, or the shared one's)."""
    return 2 * 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def router_bytes(shape: dict) -> int:
    return 4 * (shape["hidden_size"] + 1) * shape["router_width"]


def sublayers(shape: dict) -> int:
    """Layers with a latent cache: the decoder's and the MTP block's."""
    return shape["num_hidden_layers"] + shape["num_nextn_predict_layers"]


def expert_layers(shape: dict) -> int:
    return sublayers(shape) - shape["first_k_dense_replace"]


def mtp_extra_bytes(shape: dict) -> int:
    """What the MTP module holds beside its decoder block: ``eh_proj`` and
    three norms."""
    d = shape["hidden_size"]
    return 2 * (2 * d * d + 3 * d)


def table_bytes(shape: dict) -> int:
    """The embedding table, or the head."""
    return 2 * shape["vocab_size"] * shape["hidden_size"]


def outside_experts_bytes(shape: dict) -> int:
    """Every weight but the routed experts and the embedding table: what a
    step reads whatever it routes, the head once."""
    return (sublayers(shape) * attention_bytes(shape)
            + shape["first_k_dense_replace"] * mlp_bytes(shape)
            + expert_layers(shape) * (
                router_bytes(shape)
                + shape["n_shared_experts"] * expert_bytes(shape))
            + shape["num_nextn_predict_layers"] * mtp_extra_bytes(shape)
            + table_bytes(shape) + 2 * shape["hidden_size"])


def weight_bytes(shape: dict, embedding: bool = True) -> int:
    """The weights this chip holds."""
    return (outside_experts_bytes(shape)
            + expert_layers(shape) * shape["n_routed_experts"]
            * expert_bytes(shape)
            + (table_bytes(shape) if embedding else 0))


def latent_row_bytes(shape: dict) -> int:
    """One position's cache row of one layer."""
    return 2 * (shape["kv_lora_rank"] + shape["qk_rope_head_dim"])


def decode_min_bytes(shape: dict, latent_positions: float, slots: float,
                     experts_hit: float) -> float:
    """The least bytes one step must move: every layer's attention, the
    dense MLPs, the shared experts, the routers and the MTP module's own
    once; of the routed experts those the step HIT (``experts_hit``: the
    step's own counter, summed over the expert layers); the head TWICE (the
    MTP block's logits need the tokens the main model's logits gave); the
    LIVE cached rows of the active slots read once a layer
    (``latent_positions``: the step's own counter); two rows written a slot a
    layer, and the embedding rows of the slots' four tokens."""
    return float(outside_experts_bytes(shape)
                 + shape["num_nextn_predict_layers"] * table_bytes(shape)
                 + experts_hit * expert_bytes(shape)
                 + sublayers(shape) * latent_positions
                 * latent_row_bytes(shape)
                 + slots * ROWS * (sublayers(shape) * latent_row_bytes(shape)
                                   + 2 * 2 * shape["hidden_size"]))


def latent_attn_flops(shape: dict, latent_positions: float) -> float:
    """Operations of the absorbed attention over the LIVE cached rows, all
    layers, both query rows: per head and query row a position's row is
    contracted once for the score (C + dr) and once for the weighted sum of
    latents (C)."""
    c, dr = shape["kv_lora_rank"], shape["qk_rope_head_dim"]
    return (2.0 * ROWS * shape["num_attention_heads"] * (2 * c + dr)
            * latent_positions * sublayers(shape))


def draft_steps(ctx: dict) -> list:
    """The window's ``serve.engine.step`` rows on which a step landed and
    that carry this family's counters; nothing where the program writes
    none."""
    from perfbench import program_spans as ps

    return [f for f in ps.in_window(ctx, ps.STEP)
            if f.get("landed") and "drafted" in f]


def per_step(ctx: dict, field: str):
    """The mean of one counter over the steps that landed in the window (a
    row sums the steps its call landed, ``landed`` counts them)."""
    rows = draft_steps(ctx)
    steps = sum(f["landed"] for f in rows)
    return sum(f[field] for f in rows) / steps if steps else None
