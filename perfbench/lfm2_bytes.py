"""Bytes a decode step of the gated-short-convolution / attention family with
every expert of a layer held NEEDS, from shapes and the step's own counters.
Counts, not timings (``flops.py`` counts a dense decoder, ``nemotron_bytes.py``
the hybrid family, ``sala_bytes.py`` the lightning / block-sparse one,
``longcat_bytes.py`` the latent one, ``commanda_bytes.py`` the window / full
one; this file the family of ``lfm2-24b-a2b-serve1``).

``shape`` is the configuration file's published keys. Weights, pages and conv
tails are bfloat16 (2 bytes), the router and its bias float32 (4), as the
program holds them. The counts follow from shapes alone, so they are the same
whatever implements a layer. Unlike the other expert families' counts, the
experts are counted BY WHAT A STEP HIT (``experts_hit``, the step's own
counter): 64 small experts at four tokens each are not all hit in every
layer, and an expert no token picked need not be read.
"""

from __future__ import annotations


def kinds(shape: dict) -> list:
    """The type of each layer held: the first of the published list."""
    return list(shape["layer_types"])[:shape["num_hidden_layers"]]


def head_dim(shape: dict) -> int:
    return int(shape.get("head_dim")
               or shape["hidden_size"] // shape["num_attention_heads"])


def conv_bytes(shape: dict) -> int:
    """One gated short convolution: ``W_in`` (3 D wide), taps, ``W_out``."""
    d = shape["hidden_size"]
    return 2 * (4 * d * d + shape["conv_L_cache"] * d)


def attention_bytes(shape: dict) -> int:
    """One attention operator and its two head norms."""
    d, hd = shape["hidden_size"], head_dim(shape)
    return 2 * (2 * d * shape["num_attention_heads"] * hd
                + 2 * d * shape["num_key_value_heads"] * hd + 2 * hd)


def dense_mlp_bytes(shape: dict) -> int:
    return 2 * 3 * shape["hidden_size"] * shape["intermediate_size"]


def expert_bytes(shape: dict) -> int:
    """One expert's three matrices."""
    return 2 * 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def router_bytes(shape: dict) -> int:
    return 4 * (shape["hidden_size"] + 1) * shape["num_experts"]


def n_expert_layers(shape: dict) -> int:
    return max(shape["num_hidden_layers"] - shape["num_dense_layers"], 0)


def outside_experts_bytes(shape: dict) -> int:
    """Every weight a step reads whatever it routes: the operators, the
    leading dense layers' MLPs, the routers, every norm, and the embedding
    table ONCE, as the tied head."""
    d, types = shape["hidden_size"], kinds(shape)
    dense = min(shape["num_dense_layers"], len(types))
    return (types.count("conv") * conv_bytes(shape)
            + types.count("full_attention") * attention_bytes(shape)
            + dense * dense_mlp_bytes(shape)
            + n_expert_layers(shape) * router_bytes(shape)
            + 2 * (2 * d * len(types) + d)
            + 2 * shape["vocab_size"] * d)


def weight_bytes(shape: dict) -> int:
    """The weights this chip holds (``num_experts``: the experts held)."""
    return (outside_experts_bytes(shape)
            + n_expert_layers(shape) * shape["num_experts"]
            * expert_bytes(shape))


def kv_row_bytes(shape: dict) -> int:
    """One position's K and V of one attention layer."""
    return 2 * 2 * shape["num_key_value_heads"] * head_dim(shape)


def conv_tail_bytes(shape: dict, slots: float) -> float:
    """Every conv layer's tail of ``slots`` slots."""
    return (2.0 * (shape["conv_L_cache"] - 1) * shape["hidden_size"] * slots
            * kinds(shape).count("conv"))


def decode_min_bytes(shape: dict, experts_hit: float,
                     context_positions: float, slots: float) -> float:
    """The least bytes one decode step must move: every weight outside the
    experts (the tied table once, as the head), the experts that got a token
    (``experts_hit``: summed over the expert layers, the step's own counter),
    the keys and values each active slot's query attends in every attention
    layer (``context_positions`` a layer: the step's own counter), the conv
    tails read and written, and one K/V row written a slot an attention
    layer."""
    pooled = kinds(shape).count("full_attention")
    return float(outside_experts_bytes(shape)
                 + experts_hit * expert_bytes(shape)
                 + kv_row_bytes(shape) * pooled * (context_positions + slots)
                 + 2 * conv_tail_bytes(shape, slots))


def steps(ctx: dict) -> list:
    """The window's ``serve.engine.step`` rows on which a decode step landed
    and that carry this family's counters; nothing where the program writes
    none."""
    from perfbench import program_spans as ps

    return [f for f in ps.in_window(ctx, ps.STEP)
            if f.get("landed") and "expert_tokens_max" in f
            and "context_positions" in f]


def per_step(ctx: dict, field: str):
    """The mean of one counter over the decode steps that landed in the
    window (a row sums the steps its call landed, ``landed`` counts them)."""
    rows = steps(ctx)
    n = sum(f["landed"] for f in rows)
    return sum(f[field] for f in rows) / n if n else None
