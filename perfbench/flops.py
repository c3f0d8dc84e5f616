"""Operations and bytes a step NEEDS, from shapes. Counts, not timings.

``shape`` is a configuration file's published keys (``hidden_size``,
``num_hidden_layers``, ...) for a dense decoder with grouped-query attention
and a gated MLP.
"""

from __future__ import annotations


def _dims(shape: dict):
    d = shape["hidden_size"]
    hd = shape.get("head_dim") or d // shape["num_attention_heads"]
    return (d, hd, shape["num_attention_heads"], shape["num_key_value_heads"],
            shape["intermediate_size"], shape["vocab_size"],
            shape["num_hidden_layers"])


def layer_matmul_params(shape: dict) -> int:
    d, hd, nh, nkv, f, _, _ = _dims(shape)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def matmul_params(shape: dict) -> int:
    """Parameters that are multiplied: the layers' projections and the
    output head. The embedding table is looked up, not multiplied, and the
    norms' vectors are elementwise."""
    d, _, _, _, _, v, n = _dims(shape)
    return n * layer_matmul_params(shape) + d * v


def total_params(shape: dict) -> int:
    d, _, _, _, _, v, n = _dims(shape)
    tied = shape.get("tie_word_embeddings", False)
    return (n * (layer_matmul_params(shape) + 2 * d) + d
            + v * d * (1 if tied else 2))


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Model FLOPs of forward and backward per token: 6 per multiplied
    parameter, plus causal attention — QK^T and PV are 2 x 2 x L x (heads x
    head_dim) forward, half of that under the causal mask, three times that
    with the backward pass: 6 x L x heads x head_dim per layer. Recomputed
    operations (remat) do not count; the embedding lookup has none."""
    _, hd, nh, _, _, _, n = _dims(shape)
    return 6.0 * matmul_params(shape) + 6.0 * seq_len * nh * hd * n


def train_flops_per_step(shape: dict, batch: int, seq_len: int) -> float:
    return train_flops_per_token(shape, seq_len) * batch * seq_len


def decode_min_bytes(shape: dict, live_positions: int, slots: int,
                     bytes_per_el: int = 2) -> float:
    """The least bytes one decode step must READ: every multiplied weight
    and norm vector once, the embedding rows of the slots' tokens, and the
    live K and V of the active sequences (``live_positions`` = the sum of
    their lengths). Writes (one K/V row per slot per layer) and activations
    are left out: they are thousands of times smaller."""
    d, hd, _, nkv, _, _, n = _dims(shape)
    weights = matmul_params(shape) + n * 2 * d + d + slots * d
    kv = 2 * live_positions * nkv * hd * n
    return float(bytes_per_el * (weights + kv))


def mfu_pct(flops: float, seconds: float, chips: int, peak: float) -> float:
    return 100.0 * flops / (seconds * chips * peak)


def roofline_pct(min_bytes: float, seconds: float, peak_bw: float) -> float:
    return 100.0 * (min_bytes / peak_bw) / seconds
