"""Bytes a decode step of the lightning / block-sparse family NEEDS, from
shapes and the step's own counters. Counts, not timings (``flops.py`` counts
a dense decoder, ``nemotron_bytes.py`` the hybrid family; this file the
family of ``minicpm-sala-9b-serve1``).

``shape`` is the configuration file's published keys. Weights, pages and
compressed keys are bfloat16 (2 bytes), the lightning state float32 (4), as
the program holds them.
"""

from __future__ import annotations

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def _counts(shape: dict):
    kinds = shape["mixer_types"]
    return kinds.count(LIGHTNING), kinds.count(SPARSE)


def mlp_bytes(shape: dict) -> int:
    d = shape["hidden_size"]
    return 2 * (3 * d * shape["intermediate_size"] + d)


def lightning_layer_bytes(shape: dict) -> int:
    d, hd = shape["hidden_size"], shape["lightning_head_dim"]
    ld = shape["lightning_nh"] * hd
    return 2 * (4 * d * ld + ld * d + 2 * hd + ld + d) + mlp_bytes(shape)


def sparse_layer_bytes(shape: dict) -> int:
    d, hd = shape["hidden_size"], shape["head_dim"]
    q = shape["num_attention_heads"] * hd
    kv = shape["num_key_value_heads"] * hd
    return 2 * (2 * d * q + 2 * d * kv + q * d + 2 * hd + d) + mlp_bytes(shape)


def weight_bytes(shape: dict, embedding: bool = True) -> int:
    """The weights this chip holds; a decode step reads all but the
    embedding table (of which it reads one row a slot)."""
    n_l, n_s = _counts(shape)
    d = shape["hidden_size"]
    return (n_l * lightning_layer_bytes(shape) + n_s * sparse_layer_bytes(shape)
            + 2 * (shape["vocab_size"] * d * (2 if embedding else 1) + d))


def slot_state_bytes(shape: dict) -> int:
    """One slot's lightning state: [H, d, d] float32 a lightning layer."""
    n_l, _ = _counts(shape)
    hd = shape["lightning_head_dim"]
    return n_l * shape["lightning_nh"] * hd * hd * 4


def page_head_bytes(shape: dict) -> int:
    """One page of one K/V head, K and V."""
    return 2 * shape["sparse_block_size"] * shape["head_dim"] * 2


def ckey_page_head_bytes(shape: dict) -> int:
    """The compressed keys of one page of one K/V head."""
    return (shape["sparse_block_size"] // shape["sparse_kernel_stride"]
            * shape["head_dim"] * 2)


def decode_min_bytes(shape: dict, slots: float, pages_read: float,
                     pages_live: float) -> float:
    """The least bytes one decode step must move: every weight but the
    embedding table once, the lightning state of the active slots read AND
    written, the pages its sparse layers chose (``pages_read``, counted per
    K/V head and layer as the step counts them), the compressed keys of the
    pages those slots hold (``pages_live``, counted the same way), one K/V
    row written a slot and sparse layer, and the embedding rows of the
    slots' tokens. A slot in the dense regime reads its whole table, which
    the step's counters leave out: the cell this is read in has none."""
    _, n_s = _counts(shape)
    d = shape["hidden_size"]
    row = 2 * shape["num_key_value_heads"] * shape["head_dim"] * 2
    return float(weight_bytes(shape, embedding=False)
                 + 2 * slots * slot_state_bytes(shape)
                 + pages_read * page_head_bytes(shape)
                 + pages_live * ckey_page_head_bytes(shape)
                 + slots * (n_s * row + 2 * d))


def sparse_steps(ctx: dict) -> list:
    """The window's ``serve.engine.step`` rows that decoded and carry the
    sparse layers' counters; nothing where the program writes none."""
    from perfbench import program_spans as ps

    return [f for f in ps.in_window(ctx, ps.STEP)
            if f.get("active") and "sparse_pages_read" in f]
