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
    where few pairs are held and the widths are multiples of 128;
    ``moe_ffn_zero`` is that layer where the router's last outputs are
    zero-compute (identity) experts.
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
from jax.lax import axis_size as _axis_size
from jax.sharding import PartitionSpec as P


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


def sigmoid_gates(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                  k: int, scale: float, norm: bool = True,
                  eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid router with a selection bias, in float32. x: [T, D],
    w_router: [D, E], bias: [E] -> (weights [T, k], experts [T, k]). The
    top k are chosen by ``score + bias``; the weights are the chosen
    experts' scores WITHOUT the bias, normalised to sum to one where
    ``norm`` (over their sum ``+ eps``: a family's own constant), times
    ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    w_router.astype(jnp.float32)))
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), k)
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


def moe_ffn_grouped(x: jax.Array, gate_vals: jax.Array, gate_idx: jax.Array,
                    experts_held: Dict[str, jax.Array], expert_offset: int,
                    token_mask: jax.Array | None = None,
                    cap: int | None = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``moe_ffn_share``'s result by a product GROUPED by expert: the pairs
    (token, expert) are sorted with the held ones first, by expert, and each
    held expert multiplies exactly its rows (``lax.ragged_dot``), so an
    expert no token picked is not read and no row meets an expert it did
    not pick. Only the first ``cap`` sorted pairs are gathered and
    multiplied: all ``T k`` of them at a decode step's few rows, ``T`` of
    them at a prompt's (a router that spreads its picks sends ``T k Eh / E``
    pairs here, a fortieth of them at 16 of 768); should more pairs than
    ``cap`` be held, the call takes ``moe_ffn_share`` instead (one
    ``lax.cond``), so it stays dropless and exact.

    On a TPU v5e at 16 gated experts of 6144 x 2048 this was 0.48 against
    ``moe_ffn_share``'s 1.69 ms a layer at 16 rows (4 of the 16 experts hit:
    a quarter of the bytes) and 5.7 against 17.1 at 2048 (PERF.md section 5,
    PR 34); at 16 experts of 2688 x 1856 it lost at every row count (a
    width that is no multiple of 128 costs a layout copy of every expert:
    PR 33), which is why ``moe_ffn_share`` is the other form. The rows
    ``lax.ragged_dot`` leaves past its last group are UNINITIALISED on a TPU
    (inf and NaN among them): they are selected away, never multiplied by a
    zero weight. The un-sort is a gather (a scatter-add would sum in no
    fixed order)."""
    T, k = gate_idx.shape
    Eh = experts_held["w_up"].shape[0]
    cap = min(T * k, max(T, 16 * k) if cap is None else cap)
    _, _, group, sizes = _held_pairs(gate_idx, Eh, expert_offset, token_mask)
    n_held = jnp.sum(sizes)

    def grouped():
        order = jnp.argsort(group, stable=True)
        top = order[:cap]
        rows = x[top // k]                                   # [cap, D]
        u = lax.ragged_dot(rows, experts_held["w_up"], sizes)
        if "w_gate" in experts_held:
            u = jax.nn.silu(lax.ragged_dot(rows, experts_held["w_gate"],
                                           sizes)) * u
        else:
            u = relu2(u)
        y = lax.ragged_dot(u, experts_held["w_down"], sizes)
        w = gate_vals.reshape(T * k)[top]
        y = jnp.where((jnp.arange(cap) < n_held)[:, None],
                      y.astype(jnp.float32) * w[:, None], 0.0)
        at = jnp.argsort(order).reshape(T, k)   # a pair's place when sorted
        out = jnp.zeros((T, y.shape[1]), jnp.float32)
        for j in range(k):      # a token's k rows, summed in gate order
            out = out + jnp.where((at[:, j] < cap)[:, None],
                                  y[jnp.minimum(at[:, j], cap - 1)], 0.0)
        return out.astype(x.dtype)

    if cap == T * k:
        out = grouped()
    else:
        out = lax.cond(
            n_held <= cap, grouped,
            lambda: moe_ffn_share(x, gate_vals, gate_idx, experts_held,
                                  expert_offset, token_mask)[0])
    return out, jnp.sum(sizes > 0), jnp.max(sizes)


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
