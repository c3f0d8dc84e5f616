"""Bytes a decode step of the window / full attention family with held and
shared experts NEEDS, from shapes and the step's own counters. Counts, not
timings (``flops.py`` counts a dense decoder, ``nemotron_bytes.py`` the hybrid
family, ``sala_bytes.py`` the lightning / block-sparse one, ``longcat_bytes.py``
the latent one; this file the family of ``command-a-plus-serve1``).

``shape`` is the configuration file's published keys. Weights, pages and
rings are bfloat16 (2 bytes), the router float32 (4), as the program holds
them. The counts follow from shapes alone, so they are the same whatever
implements a layer.
"""

from __future__ import annotations


def kinds(shape: dict) -> list:
    """The type of each layer held: the first of the published list."""
    return list(shape["layer_types"])[:shape["num_hidden_layers"]]


def attention_bytes(shape: dict) -> int:
    """One layer's attention weights and its one norm."""
    d, hd = shape["hidden_size"], shape["head_dim"]
    return 2 * (2 * d * shape["num_attention_heads"] * hd
                + 2 * d * shape["num_key_value_heads"] * hd + d)


def expert_bytes(shape: dict) -> int:
    """One expert's three matrices, routed or shared."""
    return 2 * 3 * shape["hidden_size"] * shape["intermediate_size"]


def router_bytes(shape: dict) -> int:
    return 4 * shape["hidden_size"] * shape["router_width"]


def layer_bytes(shape: dict) -> int:
    """One layer as this chip holds it."""
    return (attention_bytes(shape) + router_bytes(shape)
            + (shape["num_shared_experts"] + shape["num_experts"])
            * expert_bytes(shape))


def weight_bytes(shape: dict) -> int:
    """The weights this chip holds. The head is the embedding table (tied),
    so a decode step reads all of them."""
    d = shape["hidden_size"]
    return (shape["num_hidden_layers"] * layer_bytes(shape)
            + 2 * (shape["vocab_size"] * d + d))


def kv_row_bytes(shape: dict) -> int:
    """One position's K and V of one layer."""
    return 2 * 2 * shape["num_key_value_heads"] * shape["head_dim"]


def decode_min_bytes(shape: dict, context_positions: float,
                     window_positions: float, slots: float) -> float:
    """The least bytes one decode step must move: every weight once (all held
    experts: the count is from shapes, not from which experts a step hit),
    the keys and values each active slot's query attends (``context_
    positions`` a full layer, ``window_positions`` a window layer: the
    step's own counters), and one row written a slot a layer."""
    types = kinds(shape)
    full, window = types.count("full_attention"), \
        types.count("sliding_attention")
    return float(weight_bytes(shape)
                 + kv_row_bytes(shape) * (full * context_positions
                                          + window * window_positions
                                          + slots * len(types)))


def steps(ctx: dict) -> list:
    """The window's ``serve.engine.step`` rows on which a decode step landed
    and that carry this family's counters; nothing where the program writes
    none."""
    from perfbench import program_spans as ps

    return [f for f in ps.in_window(ctx, ps.STEP)
            if f.get("landed") and "context_positions" in f]


def per_step(ctx: dict, field: str):
    """The mean of one counter over the decode steps that landed in the
    window (a row sums the steps its call landed, ``landed`` counts them)."""
    rows = steps(ctx)
    n = sum(f["landed"] for f in rows)
    return sum(f[field] for f in rows) / n if n else None
