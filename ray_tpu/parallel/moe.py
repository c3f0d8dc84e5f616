"""Expert parallelism: MoE routing + all_to_all dispatch over the ``ep`` axis.

The reference has no MoE/expert-parallel subsystem (SURVEY.md §2
parallelism inventory — EP "does not exist as a named subsystem"); here it
is first-class and TPU-native. Experts live sharded over the ``ep`` mesh
axis; tokens are dispatched to their routed experts with a single
``lax.all_to_all`` each way (ICI-friendly, compiled into the program by
XLA), using the capacity-buffer formulation so every shape is static.

Four implementations:
  * ``moe_ffn_share`` — the layer of ONE chip of an expert-parallel
    deployment, told which experts it holds: routes over all experts,
    computes the held experts' part of the result, dropless (every held
    expert on every row, then each row's own picked). On one chip it runs
    without its exchange. ``moe_ffn_grouped`` gives the same result by a
    product grouped by expert (each held expert on its own rows), faster
    where few pairs are held and the widths are multiples of 128; its three
    products are a Pallas grouped matmul (jax's ``megablox.gmm``) where
    ``grouped_product_form`` sees whole tiles of sorted pairs on a TPU (a
    prompt's chunk), ``lax.ragged_dot`` everywhere else (every CPU run, a
    decode step's few pairs). ``moe_ffn_zero`` is that layer where the
    router's last outputs are zero-compute (identity) experts.
  * ``moe_ffn_dense`` — computes every expert on every token and weights
    by the top-k gates. O(E) FLOPs; the correctness oracle and the
    single-device path.
  * ``ep_moe_ffn`` — capacity-based dispatch/combine inside ``shard_map``.
    Exact vs the dense path whenever no token is dropped (capacity_factor
    high enough); drops lowest-priority assignments otherwise, like
    Switch/GShard.

Tensor parallelism composes inside the expert FFN the same way as in the
pipeline stages: col-parallel gate/up, row-parallel down + psum over
``tp``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.lax import axis_size as _axis_size
from jax.sharding import PartitionSpec as P

from ..ops import attention


def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """Softmax router. x: [..., D], w_router: [D, E] -> [..., E] fp32."""
    return jax.nn.softmax(
        jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32)))


def top_k_gates(probs: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k gate values (renormalized, Mixtral-style) and expert indices."""
    vals, idx = lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx


def load_balance_loss(probs: jax.Array, gate_idx: jax.Array,
                      n_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e(frac_tokens_e * mean_prob_e)."""
    assign = jax.nn.one_hot(gate_idx[..., 0], n_experts)  # top-1 assignment
    frac_tokens = assign.reshape(-1, n_experts).mean(0)
    mean_probs = probs.reshape(-1, n_experts).mean(0)
    return n_experts * jnp.sum(frac_tokens * mean_probs)


def relu2(x: jax.Array) -> jax.Array:
    """Squared ReLU, the activation of a non-gated expert."""
    return jnp.square(jax.nn.relu(x))


def _expert_ffn(h: jax.Array, experts: Dict[str, jax.Array],
                tp_psum: bool) -> jax.Array:
    """Stacked experts. h: [E, S, D], weights [E, D, F]/[E, F, D]. SwiGLU
    where the tree holds a gate matrix, squared ReLU where it does not."""
    u = jnp.einsum("esd,edf->esf", h, experts["w_up"])
    if "w_gate" in experts:
        g = jnp.einsum("esd,edf->esf", h, experts["w_gate"])
        u = jax.nn.silu(g) * u
    else:
        u = relu2(u)
    y = jnp.einsum("esf,efd->esd", u, experts["w_down"])
    if tp_psum:
        y = lax.psum(y, "tp")
    return y


def _keep_groups(biased: jax.Array, n_group: int, topk_group: int
                 ) -> jax.Array:
    """``sigmoid_gates``' group-limited choice (DeepSeek-V3's ``noaux_tc``):
    biased [T, E] stands in ``n_group`` equal groups, a group's score is the
    sum of its two largest entries, the ``topk_group`` best groups are kept
    and every other group's entries are set to 0, so that the top k are
    chosen among what is left. One group is no limit: the array as it came."""
    if n_group == 1:
        return biased
    T, E = biased.shape
    groups = biased.reshape(T, n_group, E // n_group)
    score = lax.top_k(groups, 2)[0].sum(-1)                     # [T, n_group]
    _, best = lax.top_k(score, topk_group)
    keep = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], groups, 0.0).reshape(T, E)


def sigmoid_gates(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                  k: int, scale: float, norm: bool = True,
                  eps: float = 1e-20, n_group: int = 1, topk_group: int = 1
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid router with a selection bias, in float32. x: [T, D],
    w_router: [D, E], bias: [E] -> (weights [T, k], experts [T, k]). The
    top k are chosen by ``score + bias``, among the ``topk_group`` best of
    ``n_group`` groups where there is more than one (``_keep_groups``); the
    weights are the chosen experts' scores WITHOUT the bias, normalised to
    sum to one where ``norm`` (over their sum ``+ eps``: a family's own
    constant), times ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    w_router.astype(jnp.float32)))
    biased = _keep_groups(scores + bias.astype(jnp.float32), n_group,
                          topk_group)
    _, idx = lax.top_k(biased, k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        vals = vals / (vals.sum(-1, keepdims=True) + eps)
    return vals * scale, idx


def balanced_bias(scores: jax.Array, k: int) -> jax.Array:
    """The selection bias that makes a router's outputs be chosen about
    equally often over a sample. scores [T, E]: each output's score over
    ``T`` sample tokens -> [E]: the offset that puts the ``1 - k / E``
    quantile of every output's score where the others' is, so that each is
    over the common threshold for ``k / E`` of the tokens, as load balancing
    leaves a trained router. What a family's ``calibrate_router_bias`` sets
    at each expert layer of its seeded weights; the bias only selects."""
    cut = jnp.quantile(scores, 1.0 - k / scores.shape[-1], axis=0)
    return jnp.mean(cut) - cut


def _held_pairs(gate_idx: jax.Array, Eh: int, expert_offset: int,
                token_mask: jax.Array | None):
    """Of the pairs (token, expert) [T, k], those whose expert is one of the
    ``Eh`` held from ``expert_offset`` (and whose lane is not masked out):
    (the held experts' local index, which pairs are held, each pair's group
    [T k] with ``Eh`` for "computed nowhere", the pairs of each held expert
    int32[Eh])."""
    T, k = gate_idx.shape
    local = gate_idx - expert_offset
    held = (local >= 0) & (local < Eh)
    if token_mask is not None:
        held = held & token_mask[:, None]
    group = jnp.where(held, local, Eh).reshape(T * k)
    sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
    return local, held, group, sizes


def moe_ffn_share(x: jax.Array, gate_vals: jax.Array, gate_idx: jax.Array,
                  experts_held: Dict[str, jax.Array], expert_offset: int,
                  token_mask: jax.Array | None = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed part of the result that THIS chip's experts give.

    x: [T, D]; gate_vals / gate_idx: [T, k] over ALL experts of the layer;
    experts_held: ``w_up`` [Eh, D, F] and ``w_down`` [Eh, F, D] of experts
    ``expert_offset .. expert_offset + Eh - 1``. A pair (token, expert)
    whose expert is not held here adds nothing: in the deployment another
    chip computes it. Dropless: every held expert multiplies every row, the
    weights read where and as the tree stores them, and each pair's row is
    picked from the result, so one expert may get every token. That costs
    ``Eh / k`` times the arithmetic of a product grouped by expert
    (``lax.ragged_dot`` over the sorted pairs, which this was until PR 33)
    and was faster at every row count an engine runs: 0.44 against 1.75 ms
    a layer at 16 rows, 2.75 against 5.27 at 1280, 16 experts of 2688 x 1856
    on a TPU v5e (PERF.md section 5). The gate is applied in float32 after
    the second product and a token's ``k`` rows are summed in gate order.
    ``token_mask`` [T] leaves a lane out (an engine's empty slot). Returns
    (out [T, D], the held experts that got a token, the most tokens one
    expert got).
    """
    Eh = experts_held["w_up"].shape[0]
    local, held, _, sizes = _held_pairs(gate_idx, Eh, expert_offset,
                                        token_mask)
    y = _expert_ffn(jnp.broadcast_to(x, (Eh,) + x.shape), experts_held,
                    tp_psum=False)                       # [Eh, T, D]
    rows = jnp.take_along_axis(
        y, jnp.where(held, local, 0).T[:, :, None], axis=0)      # [k, T, D]
    out = jnp.where(held.T[:, :, None],
                    rows.astype(jnp.float32) * gate_vals.T[:, :, None],
                    0.0).sum(axis=0)
    return out.astype(x.dtype), jnp.sum(sizes > 0), jnp.max(sizes)


def softmax_gates(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                  k: int, scale: float) -> Tuple[jax.Array, jax.Array]:
    """Softmax router with a selection bias, in float32, over the router's
    whole width (zero-compute experts among them). x: [T, D], w_router:
    [D, E], bias: [E] -> (weights [T, k], experts [T, k]). The top k are
    chosen by ``score + bias``; the weights are the chosen experts' scores
    WITHOUT the bias, NOT normalised, times ``scale``."""
    scores = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                                    w_router.astype(jnp.float32)), axis=-1)
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), k)
    return jnp.take_along_axis(scores, idx, axis=-1) * scale, idx


#: rows of a tile of the kernel's sorted pairs: the MXU's width. A group's
#: two boundary tiles are multiplied whole, so a taller tile multiplies more
#: rows of other groups: at 128 / 256 / 512 rows a tile the first product of
#: 64 groups of ~128 rows took 0.95 / 0.99 / 1.40 ms and of 36 groups of
#: ~284 rows 0.76 / 0.78 / (no VMEM) (chip, PR 57). Whole tiles of it are
#: all the kernel asks of the pairs' count: alone on a TPU v5e (my chip run,
#: PR 57; PERF.md section 5), a layer in ms, this against the parent's layer:
#: 64 gated experts of 2048 x 1536 all held, top-4, 256 pairs 1.71 / 2.47,
#: 1024 pairs 1.84 / 3.95, 8192 (a chunk) 2.79 / 5.00; 36 of 72 of 4096 x
#: 768, top-10, 640 pairs 1.05 / 1.66, 20 480 (a chunk) 4.03 / 7.74; 16 of
#: 128 of 4096 x 4096, top-8, 128 pairs 2.25 / 2.72, 4096 (a chunk) 4.43 /
#: 7.68; 16 of 768 of 6144 x 2048, top-12, 256 pairs 1.94 / 2.62, 2048 (a
#: chunk) 3.37 / 6.35: it won at every whole-tile count, one tile included.
#: The one engine program that groups no whole tile (LongCat's decode step:
#: 16 rows, 192 pairs, 0.49 ms a layer for its four hit experts, near their
#: bytes) stays ``lax.ragged_dot``
KERNEL_ROW_TILE = 128
#: what a call of the kernel may take of the 16 MiB of scoped VMEM a Mosaic
#: call gets on a v5e, counted as ``_kernel_tiling`` does: the weight tile
#: and the output tile twice (double-buffered), the row tile twice and once
#: more on the kernel's stack. Against the sizes Mosaic reports where it
#: refuses a tile (AOT for a described v5e, PR 57: bfloat16 and float32,
#: contractions of 128 to 6144) the count read from 0.7 % under to 5 % over
#: at nine refused tiles, hence the quarter MiB left; twelve tiles it admits
#: compiled, the eight the four families' widths give among them
KERNEL_VMEM_BYTES = (16 << 20) - (256 << 10)


def _kernel_tiling(K: int, N: int, itemsize: int) -> Tuple[int, int, int]:
    """(row, contraction, column) tile of one grouped product [cap, K] x
    [Eh, K, N], from the widths alone. The contraction is ONE tile: an
    expert's weight tile then changes only where the group does, so each
    held expert is read once a column tile however many row tiles its group
    touches (split in two, the weights of 36 experts of 4096 x 768 were
    read at every visit: 1.23 against 0.76 ms). The column tile is the
    widest whole-lane divisor of ``N`` whose call fits
    ``KERNEL_VMEM_BYTES``: the rows are read once a column tile, and a grid
    step costs ~0.35 us (0.76 / 0.80 / 0.85 / 1.12 ms at 768 / 384 / 256 /
    128 columns); 0 where not even one lane-wide tile fits."""
    tm = KERNEL_ROW_TILE
    tn = max((t for t in range(128, N + 1, 128) if N % t == 0
              and itemsize * (3 * tm * K + 2 * K * t + 2 * tm * t)
              <= KERNEL_VMEM_BYTES), default=0)
    return tm, K, tn


def grouped_product_form(cap: int, D: int, F: int, dtype) -> str:
    """The implementation of ``moe_ffn_grouped``'s three products, from what
    the call sees of its input and nothing else: ``"kernel"``, the Pallas
    grouped matmul, where the ``cap`` sorted pairs are whole row tiles, both
    widths of the experts ``[D, F]`` are whole lanes and a tile of either
    whole contraction fits (one test: a column tile is a whole-lane divisor
    of the other width), the dtype is floating, and the platform is a TPU
    (it answers through ``ops.attention._on_tpu``, the one function a test
    replaces); ``"ragged"``, ``lax.ragged_dot``, everywhere else: every CPU
    run, a decode step's few pairs, a width that is no multiple of 128. The
    number of experts does not enter: the one row tile serves groups of 32
    to 284 rows (``KERNEL_ROW_TILE``)."""
    itemsize = jnp.dtype(dtype).itemsize
    tiles = (cap % KERNEL_ROW_TILE == 0
             and _kernel_tiling(D, F, itemsize)[2] > 0
             and _kernel_tiling(F, D, itemsize)[2] > 0)
    return "kernel" if (tiles and jnp.issubdtype(dtype, jnp.floating)
                        and attention._on_tpu()) else "ragged"


def grouped_pairs(T: int, k: int, cap: int | None = None) -> int:
    """The sorted pairs ``moe_ffn_grouped`` gathers and multiplies at ``T``
    rows of ``k`` picks when called with ``cap``."""
    return min(T * k, max(T, 16 * k) if cap is None else cap)


def _kernel_dot(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                ) -> jax.Array:
    """``lax.ragged_dot(lhs, rhs, sizes)`` by the Pallas grouped matmul that
    jax ships (``megablox.gmm``): row tiles of the sorted pairs, each tile's
    expert (and a boundary tile's second one, its rows masked at the store)
    found by scalar prefetch from the group sizes, the expert's weight tile
    read from ``rhs`` as stored, float32 accumulation, ``lhs``'s dtype out.
    The tiles past the last group are not visited: their rows of the result
    are whatever the buffer held. Interpreted where there is no Mosaic
    compiler (a test that forces the form on the CPU)."""
    return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
               tiling=_kernel_tiling(*rhs.shape[1:],
                                     jnp.dtype(rhs.dtype).itemsize),
               interpret=not attention._on_tpu())


def moe_ffn_grouped(x: jax.Array, gate_vals: jax.Array, gate_idx: jax.Array,
                    experts_held: Dict[str, jax.Array], expert_offset: int,
                    token_mask: jax.Array | None = None,
                    cap: int | None = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``moe_ffn_share``'s result by a product GROUPED by expert: the pairs
    (token, expert) are sorted with the held ones first, by expert, and each
    held expert multiplies exactly its rows, so an expert no token picked is
    not read and no row meets an expert it did not pick. Only the first
    ``cap`` sorted pairs are gathered and multiplied: all ``T k`` of them at
    a decode step's few rows, ``T`` of them at a prompt's (a router that
    spreads its picks sends ``T k Eh / E`` pairs here, a fortieth of them at
    16 of 768); should more pairs than ``cap`` be held, the call takes
    ``moe_ffn_share`` instead (one ``lax.cond``), so it stays dropless and
    exact.

    The three products take the form ``grouped_product_form`` picks from the
    shapes, the dtype and the platform: the Pallas grouped matmul
    (``_kernel_dot``) at a chunk's sorted pairs on a TPU, ``lax.ragged_dot``
    otherwise; same pairs, same operands (the weights as the tree stores
    them), float32 accumulation, the input's dtype out. A layer alone on a
    TPU v5e at a chunk's 2048 rows, this against the parent's layer (my chip
    run, PR 57; PERF.md section 5): 36 of 72 gated experts of 4096 x 768,
    top-10, 4.03 against 7.74 ms; 64 of 2048 x 1536 all held, top-4, 2.79
    against 5.00; 16 of 128 of 4096 x 4096, top-8, 4.43 against 7.68; 16 of
    768 of 6144 x 2048, top-12, 3.37 against 6.35. The two forms were equal
    on that sweep's data and, on one model's weights, in every calibrated
    bias, row and token (PERF.md section 6); nothing holds them to one order
    of a float32 sum, so no caller may count on equal bits. Before it
    (PR 34, ``ragged_dot`` alone) 16 gated experts of 6144 x 2048 were 0.48
    against ``moe_ffn_share``'s 1.69 ms a layer at 16 rows (4 of the 16
    experts hit: a quarter of the bytes), which stands, and 5.7 against 17.1
    at 2048; at 16 experts of 2688 x 1856 the grouped product lost at every
    row count (a width that is no multiple of 128 costs a layout copy of
    every expert: PR 33), which is why ``moe_ffn_share`` is the other form
    and why such widths never take the kernel.

    The rows past the last group are UNINITIALISED in BOTH forms on a TPU
    (``lax.ragged_dot`` leaves them, the kernel never visits their tiles;
    inf and NaN among them): they are selected away, never multiplied by a
    zero weight. The un-sort is ONE gather (a scatter-add would sum in no
    fixed order) of the last product's rows in their own dtype, the gate
    applied after it: pair (t, j) lies at ``argsort(order)[t k + j]`` and
    its gate is ``gate_vals[t, j]``. Until PR 57 the gate was applied before
    it, to a float32 array of every sorted pair that was then gathered from
    ``k`` times: 2.66 ms of a layer at 20 480 pairs of 4096 against 1.4 so;
    the same sum, compiled otherwise: rare bf16 steps apart (chip, PR 57)."""
    T, k = gate_idx.shape
    Eh, D, F = experts_held["w_up"].shape
    cap = grouped_pairs(T, k, cap)
    _, _, group, sizes = _held_pairs(gate_idx, Eh, expert_offset, token_mask)
    n_held = jnp.sum(sizes)
    dot = (_kernel_dot if grouped_product_form(cap, D, F, x.dtype) == "kernel"
           else lax.ragged_dot)

    def grouped():
        order = jnp.argsort(group, stable=True)
        rows = x[order[:cap] // k]                           # [cap, D]
        u = dot(rows, experts_held["w_up"], sizes)
        if "w_gate" in experts_held:
            u = jax.nn.silu(dot(rows, experts_held["w_gate"], sizes)) * u
        else:
            u = relu2(u)
        y = dot(u, experts_held["w_down"], sizes)
        at = jnp.argsort(order).reshape(T, k)   # a pair's place when sorted
        picked = y[jnp.minimum(at, cap - 1).T]                  # [k, T, D]
        out = jnp.zeros((T, D), jnp.float32)
        for j in range(k):      # a token's k rows, summed in gate order
            out = out + jnp.where(
                (at[:, j] < n_held)[:, None],
                picked[j].astype(jnp.float32) * gate_vals[:, j, None], 0.0)
        return out.astype(x.dtype)

    if cap == T * k:
        out = grouped()
    else:
        out = lax.cond(
            n_held <= cap, grouped,
            lambda: moe_ffn_share(x, gate_vals, gate_idx, experts_held,
                                  expert_offset, token_mask)[0])
    return out, jnp.sum(sizes > 0), jnp.max(sizes)


def held_experts_form(T: int, k: int, Eh: int, D: int, F: int, E: int, dtype,
                      grouped_from: int) -> Tuple[str | None, int]:
    """How ``moe_ffn_held`` multiplies ``T`` rows of ``k`` picks by ``Eh``
    held experts ``[D, F]`` of a router ``E`` wide: (the form of the grouped
    products, ``grouped_product_form``'s answer, and the pairs they are given
    room for), or (None, 0) under ``grouped_from`` rows, where every row
    meets every held expert. The room is twice the pairs a router that
    spreads its picks sends here: every pair where half the experts or more
    are held, so no fallback is compiled beside such a product. The engine
    asks it with a family's chunk for its ``serve.admit.prefill`` rows."""
    if T < grouped_from:
        return None, 0
    cap = min(T * k, 2 * -(-T * k * Eh // E))
    return grouped_product_form(cap, D, F, dtype), cap


def moe_ffn_held(x: jax.Array, gate_vals: jax.Array, gate_idx: jax.Array,
                 experts_held: Dict[str, jax.Array], expert_offset: int,
                 token_mask: jax.Array | None, router_width: int,
                 grouped_from: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One chip's routed experts of a family that measured both forms alone:
    ``moe_ffn_share`` under ``grouped_from`` rows (a decode step's),
    ``moe_ffn_grouped`` from there (a prompt's chunk), as
    ``held_experts_form`` says."""
    form, cap = held_experts_form(
        *gate_idx.shape, *experts_held["w_up"].shape, router_width, x.dtype,
        grouped_from)
    if form is None:
        return moe_ffn_share(x, gate_vals, gate_idx, experts_held,
                             expert_offset, token_mask)
    return moe_ffn_grouped(x, gate_vals, gate_idx, experts_held,
                           expert_offset, token_mask, cap)


def moe_ffn_zero(x: jax.Array, gate_vals: jax.Array, gate_idx: jax.Array,
                 experts_held: Dict[str, jax.Array], expert_offset: int,
                 n_real: int, token_mask: jax.Array | None = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The layer of one chip where the router's outputs from ``n_real`` on
    are **zero-compute (identity) experts**: a pair (token, expert) with
    ``expert >= n_real`` adds ``gate x input``. Such a pair needs no
    exchange, so in the deployment a token's home chip adds it, and this
    chip adds it whole for its own tokens (as a shared expert is counted
    once); a held pair adds the held expert's result (``moe_ffn_grouped``:
    with a third of the picks computing nothing and 16 of 512 computing
    experts here, few pairs are held), an absent pair nothing. Returns (out
    [T, D], held experts hit, most tokens of one expert, the pairs routed to
    zero experts)."""
    routed, hit, most = moe_ffn_grouped(x, gate_vals, gate_idx, experts_held,
                                        expert_offset, token_mask)
    with jax.named_scope("zero_experts"):
        zero = gate_idx >= n_real
        if token_mask is not None:
            zero = zero & token_mask[:, None]
        gate = jnp.where(zero, gate_vals, 0.0).sum(axis=-1)
        out = routed.astype(jnp.float32) \
            + x.astype(jnp.float32) * gate[:, None]
    return out.astype(x.dtype), hit, most, jnp.sum(zero)


def moe_ffn_dense(x: jax.Array, w_router: jax.Array,
                  experts: Dict[str, jax.Array], k: int, gates=None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Reference MoE: all experts computed, gated by top-k weights.

    x: [B, L, D]; experts leaves have leading dim E. ``gates``: (values,
    experts) of [B, L, k] from another router (``sigmoid_gates``), in place
    of the softmax router's own.
    Returns (out [B, L, D], aux_loss scalar).
    """
    E = w_router.shape[1]
    probs = router_probs(x, w_router)
    gate_vals, gate_idx = top_k_gates(probs, k) if gates is None else gates
    gates = jnp.sum(
        jax.nn.one_hot(gate_idx, E) * gate_vals[..., None], axis=-2)  # [B,L,E]
    B, L, D = x.shape
    y = _expert_ffn(jnp.repeat(x.reshape(1, B * L, D), E, axis=0),
                    experts, tp_psum=False)  # [E, B*L, D]
    out = jnp.einsum("te,etd->td", gates.reshape(B * L, E).astype(y.dtype),
                     y).reshape(B, L, D)
    aux = load_balance_loss(probs, gate_idx, E)
    return out.astype(x.dtype), aux


def default_capacity(tokens_per_device: int, n_experts: int, k: int,
                     capacity_factor: float) -> int:
    """Static per-expert capacity *per device* (GShard convention): each
    device may send at most C of its tokens to any one expert, so an
    expert's total buffer across the group is ep * C = cf * total * k / E."""
    return max(k, int(math.ceil(
        capacity_factor * tokens_per_device * k / n_experts)))


def ep_moe_ffn(x: jax.Array, w_router: jax.Array,
               experts_local: Dict[str, jax.Array], k: int,
               capacity: int, axis: str = "ep", tp_psum: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE inside ``shard_map``.

    x: [B_local, L, D] (this device's token shard — ``ep`` doubles as a
    data axis for non-MoE compute, so tokens are already distributed).
    experts_local: this device's expert shard, leading dim E/ep.
    Returns (out [B_local, L, D], aux_loss scalar, psum-averaged over ep).
    """
    ep = _axis_size(axis)
    E = w_router.shape[1]
    E_local = E // ep
    B, L, D = x.shape
    T = B * L
    xt = x.reshape(T, D)

    probs = router_probs(xt, w_router)           # [T, E]
    gate_vals, gate_idx = top_k_gates(probs, k)  # [T, k]
    mask = jax.nn.one_hot(gate_idx, E)           # [T, k, E]

    # Capacity assignment: earlier gate slots get priority, then token
    # order (GShard). dispatch/combine: [T, E, C].
    counts = jnp.zeros((E,), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    for j in range(k):
        m = mask[:, j]                                  # [T, E]
        pos = jnp.cumsum(m, axis=0) - 1 + counts[None]  # queue position
        counts = counts + m.sum(0)
        keep = m * (pos < capacity)
        slot = jax.nn.one_hot((pos * m).sum(-1).astype(jnp.int32), capacity)
        d_j = keep[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + d_j * gate_vals[:, j][:, None, None]

    # Gather each expert's token buffer, then exchange so every device
    # holds the full (ep * C) buffer for its local experts.
    buf = jnp.einsum("tec,td->ecd", dispatch, xt.astype(jnp.float32))
    buf = buf.reshape(ep, E_local, capacity, D)
    buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)
    buf = buf.transpose(1, 0, 2, 3).reshape(E_local, ep * capacity, D)

    y = _expert_ffn(buf.astype(x.dtype), experts_local, tp_psum=tp_psum)

    # Route results back to the owning tokens.
    y = y.astype(jnp.float32).reshape(E_local, ep, capacity, D)
    y = y.transpose(1, 0, 2, 3)
    y = lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
    y = y.reshape(E, capacity, D)
    out = jnp.einsum("tec,ecd->td", combine, y).reshape(B, L, D)

    aux = load_balance_loss(probs, gate_idx, E)
    aux = lax.pmean(aux, axis)
    return out.astype(x.dtype), aux


def make_ep_moe_ffn(mesh, k: int, capacity_factor: float = 2.0,
                    batch_axes=("dp", "fsdp", "ep")):
    """shard_map-wrapped expert-parallel MoE over a full mesh.

    Takes global arrays: x [B, L, D] (batch sharded over ``batch_axes``),
    w_router [D, E] replicated, experts tree with leading dim E sharded
    over ``ep`` (and tp on the ffn dims). Returns (out, aux).
    """
    tp = mesh.shape["tp"]

    expert_specs = {
        "w_gate": P("ep", None, "tp"),
        "w_up": P("ep", None, "tp"),
        "w_down": P("ep", "tp", None),
    }

    def fn(x, w_router, experts):
        E = w_router.shape[1]
        n_data = math.prod(mesh.shape[a] for a in batch_axes)
        tokens_local = (x.shape[0] // n_data) * x.shape[1]
        capacity = default_capacity(tokens_local, E, k, capacity_factor)

        def local(x, w_router, experts_local):
            out, aux = ep_moe_ffn(x, w_router, experts_local, k, capacity,
                                  tp_psum=tp > 1)
            # ep_moe_ffn pmeans over ep; the other data axes hold different
            # token shards, so average those too before claiming P().
            for a in batch_axes:
                if a != "ep":
                    aux = lax.pmean(aux, a)
            return out, aux

        out, aux = shard_map(
            local, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(), expert_specs),
            out_specs=(P(batch_axes, None, None), P()),
            check_vma=False,
        )(x, w_router, experts)
        return out, aux

    return fn


def expert_share(params: Dict[str, Any], offset: int, held: int
                 ) -> Dict[str, Any]:
    """The tree of one chip of a deployment that divides each layer's
    computing experts: experts ``offset .. offset + held - 1`` of a tree
    whose layers each hold them all under ``"moe"`` (``w_gate`` / ``w_up`` /
    ``w_down``, the expert axis first); everything else (attention, shared
    and zero experts, the router over all outputs, the norms) is on every
    chip alike."""
    layers = [{**lyr, "moe": {
        **lyr["moe"], **{w: lyr["moe"][w][offset:offset + held]
                         for w in ("w_gate", "w_up", "w_down")}}}
        if "moe" in lyr else lyr    # a leading dense layer has no experts
        for lyr in params["layers"]]
    return {**params, "layers": layers}


def expert_shardings(experts: Any, mesh) -> Any:
    """NamedShardings for a stacked expert tree: dim 0 -> ep, ffn dims tp."""
    from jax.sharding import NamedSharding

    from .sharding import clean_spec

    specs = {
        "w_gate": P("ep", "fsdp", "tp"),
        "w_up": P("ep", "fsdp", "tp"),
        "w_down": P("ep", "tp", "fsdp"),
    }

    def one(name, leaf):
        return NamedSharding(
            mesh, clean_spec(specs.get(name, P("ep")), leaf.shape, mesh))

    return {name: one(name, leaf) for name, leaf in experts.items()}

