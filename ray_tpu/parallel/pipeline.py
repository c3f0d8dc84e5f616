"""Pipeline parallelism: SPMD GPipe schedule over the ``pp`` mesh axis.

The reference has no pipeline parallelism of its own (SURVEY.md §2
parallelism inventory) — it only ships the NCCL p2p channels
(``experimental/channel/nccl_group.py:162-256``) that external libraries
build pipelines on. Here PP is first-class and TPU-native: every pipeline
stage is the *same* XLA program (SPMD), stage-to-stage transfer is a single
``lax.ppermute`` hop on the ``pp`` axis (ICI-adjacent by mesh construction,
see ``mesh.make_mesh``), and the microbatch schedule is a ``lax.scan`` so
the whole pipeline — all stages, all ticks — is one compiled program that
XLA can overlap (permute DMA in flight while the next microbatch computes).

Schedule: GPipe with M microbatches over S stages = M + S - 1 ticks;
bubble fraction (S-1)/(M+S-1). Under ``jax.grad`` the backward pipeline
falls out of autodiff-through-scan (reverse schedule, same permutes
reversed); ``jax.checkpoint`` on the stage body keeps activation memory at
one microbatch per stage.

Cross-slice (DCN) pipelines — where one XLA program cannot span the
slices — use the MPMD actor path instead: ``ray_tpu.dag`` compiled actor
pipelines with stage-to-stage channels (SURVEY.md §7 hard part 4).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import PartitionSpec as P


def stack_layers(layers: Sequence[Any]) -> Any:
    """[L] list of identically-shaped layer pytrees -> one stacked pytree.

    Leaves gain a leading layer axis; shard it over ``pp`` to place L/S
    consecutive layers on each stage.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked: Any) -> List[Any]:
    """Inverse of :func:`stack_layers`."""
    leaves = jax.tree.leaves(stacked)
    n = leaves[0].shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def make_stage_fn(layer_fn: Callable[[Any, jax.Array], jax.Array],
                  remat: bool = True) -> Callable[[Any, jax.Array], jax.Array]:
    """Stage body: scan ``layer_fn`` over this stage's local layer stack.

    ``layer_fn(layer_params, x) -> x`` is one transformer block; the stage
    holds a [layers_per_stage, ...] stacked pytree (the local ``pp`` shard).
    """
    def body(x, layer):
        fn = jax.checkpoint(layer_fn) if remat else layer_fn
        return fn(layer, x), None

    def stage_fn(stage_params, x):
        x, _ = lax.scan(body, x, stage_params)
        return x

    return stage_fn


def spmd_pipeline(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any, microbatches: jax.Array,
                  axis: str = "pp") -> jax.Array:
    """Run the GPipe schedule. Call inside ``shard_map``.

    Args:
      stage_fn: ``(local_stage_params, x) -> y`` with ``y.shape == x.shape``
        (transformer blocks; embed/head live outside the pipeline).
      stage_params: this device's stage shard (leading layer axis already
        local, i.e. sharded over ``axis`` at the shard_map boundary).
      microbatches: [M, mb, ...] — the full local-batch microbatch queue
        (replicated across ``axis``; only stage 0 consumes it).
    Returns: [M, mb, ...] outputs, identical on every ``axis`` member.
    """
    pp = _axis_size(axis)
    idx = lax.axis_index(axis)
    M = microbatches.shape[0]
    fwd = [(j, (j + 1) % pp) for j in range(pp)]

    def tick(carry, t):
        prev_out, outputs = carry
        # Stage 0 pulls microbatch t from its queue; later stages consume
        # the activation permuted in at the end of the previous tick.
        feed = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        x_in = jnp.where(idx == 0, feed, prev_out)
        y = stage_fn(stage_params, x_in)
        # The last stage finishes microbatch m = t - (pp-1) at tick t.
        m_out = t - (pp - 1)
        slot = jnp.clip(m_out, 0, M - 1)
        cur = lax.dynamic_index_in_dim(outputs, slot, 0, keepdims=False)
        done = jnp.logical_and(idx == pp - 1, m_out >= 0)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(done, y, cur), slot, 0)
        nxt = lax.ppermute(y, axis, fwd)
        return (nxt, outputs), None

    zeros = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = lax.scan(
        tick, (zeros, out0), jnp.arange(M + pp - 1))
    # Results live on the last stage; broadcast around the ring so the
    # (replicated-over-pp) head/loss can run everywhere. One hop per stage
    # of batch-sized data — noise next to the per-tick activation traffic.
    outputs = lax.psum(
        jnp.where(idx == pp - 1, outputs, jnp.zeros_like(outputs)), axis)
    return outputs


def pipeline_shardings(stacked_layers: Any, mesh, rules=None) -> Any:
    """NamedShardings for a stacked layer tree: axis 0 -> ``pp``, remaining
    dims follow the tensor-parallel rules from ``sharding.spec_for``."""
    from jax.sharding import NamedSharding

    from .sharding import LLAMA_RULES, _tree_paths, clean_spec, spec_for

    rules = rules or LLAMA_RULES
    paths = _tree_paths(stacked_layers)

    def one(path, leaf):
        if leaf.shape[0] % mesh.shape["pp"]:
            raise ValueError(
                f"{path}: {leaf.shape[0]} layers not divisible by "
                f"pp={mesh.shape['pp']}")
        spec = clean_spec(spec_for(path, rules), leaf.shape[1:], mesh)
        return NamedSharding(mesh, P("pp", *spec))

    return jax.tree.map(one, paths, stacked_layers)


def _tp_layer_fn(layer, x, cos, sin, cfg, attn_impl):
    """One transformer block with megatron TP inside ``shard_map``.

    Weights arrive tp-sharded (qkv/gate/up col-parallel, wo/down
    row-parallel per ``sharding.LLAMA_RULES``), so head/ff dims are local
    slices and row-parallel matmuls finish with a ``psum`` over ``tp``
    (no-op when tp=1). Head counts derive from local shapes, not ``cfg``.
    """
    from ..ops.layers import apply_rope, rms_norm

    B, L, _ = x.shape
    hd = cfg.head_dim
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = jnp.dot(h, layer["wq"]).reshape(B, L, -1, hd)
    k = jnp.dot(h, layer["wk"]).reshape(B, L, -1, hd)
    v = jnp.dot(h, layer["wv"]).reshape(B, L, -1, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = attn_impl(q, k, v, causal=True)
    o = o.reshape(B, L, -1)
    x = x + lax.psum(jnp.dot(o, layer["wo"]), "tp")
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    g = jnp.dot(h, layer["w_gate"])
    u = jnp.dot(h, layer["w_up"])
    mlp = lax.psum(jnp.dot(jax.nn.silu(g) * u, layer["w_down"]), "tp")
    return x + mlp


def _stacked_in_specs(stacked_layers: Any, mesh) -> Any:
    """shard_map in_specs for the stacked tree: keep ``pp`` + ``tp``
    components (tp stays sharded for in-stage TP); fsdp dims fall off the
    spec so jit all-gathers them at the boundary — exactly ZeRO-3
    semantics (gather params for compute, keep them sharded at rest)."""
    sh = pipeline_shardings(stacked_layers, mesh)

    def keep(ns):
        out = [ns.spec[0]]  # "pp"
        for axis in ns.spec[1:]:
            axes = axis if isinstance(axis, tuple) else (axis,)
            out.append("tp" if "tp" in axes else None)
        return P(*out)

    return jax.tree.map(keep, sh)


def make_pipelined_loss(mesh, cfg, n_microbatches: int,
                        remat: bool = True, attn_impl=None):
    """Llama loss with layers pipelined over ``pp`` and TP inside stages.

    Params layout: ``{"embedding", "norm", ["lm_head"], "stacked": tree}``
    where ``stacked`` is :func:`stack_layers` of the per-layer dicts with
    leading axis sharded over ``pp`` (see :func:`pipeline_shardings`).
    Embed/head/norm live outside the pipeline (they shard over tp/fsdp as
    usual via ``sharding.shardings_for_tree``). Composes pp x tp x dp x
    fsdp: tp runs megatron-style inside each stage (``_tp_layer_fn``),
    fsdp params are boundary-gathered, batch shards over dp/fsdp.
    """
    from ..models.llama import next_token_targets
    from ..ops.attention import flash_attention
    from ..ops.layers import cross_entropy_loss, rms_norm, rope_frequencies

    if attn_impl is None:
        attn_impl = flash_attention
    pp = mesh.shape["pp"]
    if cfg.n_layers % pp:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={pp}")
    tp = mesh.shape["tp"]
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            f"heads ({cfg.n_heads}/{cfg.n_kv_heads}) not divisible by "
            f"tp={tp}")
    if cfg.d_ff % tp:
        # clean_spec would silently drop the tp sharding while the stage
        # body still psums over tp, double-counting the MLP.
        raise ValueError(f"d_ff={cfg.d_ff} not divisible by tp={tp}")

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        targets = batch.get("targets")
        if targets is None:
            targets = next_token_targets(tokens)
        B, L = tokens.shape
        cos, sin = rope_frequencies(cfg.head_dim, L, cfg.rope_theta)
        x = params["embedding"][tokens].astype(cfg.dtype)

        def run_pipe(stacked_local, x, cos, sin):
            def layer_fn(layer, x):
                return _tp_layer_fn(layer, x, cos, sin, cfg, attn_impl)

            stage_fn = make_stage_fn(layer_fn, remat=remat)
            b = x.shape[0]
            if b % n_microbatches:
                raise ValueError(
                    f"local batch {b} not divisible into {n_microbatches} "
                    "microbatches")
            mb = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])
            out = spmd_pipeline(stage_fn, stacked_local, mb)
            return out.reshape(x.shape)

        x = shard_map(
            run_pipe, mesh=mesh,
            in_specs=(_stacked_in_specs(params["stacked"], mesh),
                      P(("dp", "fsdp"), None, None), P(), P()),
            out_specs=P(("dp", "fsdp"), None, None),
            check_vma=False,
        )(params["stacked"], x, cos, sin)

        x = rms_norm(x, params["norm"], cfg.norm_eps)
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.dot(x, head.astype(x.dtype))
        loss, _ = cross_entropy_loss(logits, targets)
        return loss

    return loss_fn


def to_pipeline_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Convert a flat Llama params dict (list of layers) into the pipelined
    layout consumed by :func:`make_pipelined_loss`."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["stacked"] = stack_layers(params["layers"])
    return out
