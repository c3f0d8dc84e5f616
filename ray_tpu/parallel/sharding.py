"""Parameter/activation sharding rules: DP / FSDP / TP as GSPMD specs.

The reference delegates tensor/expert/pipeline parallelism to user libraries
(SURVEY.md §2: "TP/PP/SP/EP do not exist as named subsystems"); here they are
first-class. Rules map parameter-name patterns to ``PartitionSpec``s and XLA
inserts the collectives — the compiled analog of torch DDP/FSDP wrappers
(``train/torch/config.py``, ``rllib/core/learner/torch/torch_learner.py:29``).

Which collectives, for the sharded training step (``LLAMA_RULES`` on
``fsdp`` x ``tp``): a layer's weights are all-gathered over ``fsdp`` where
they are used (forward, remat and backward: ZeRO-3), their gradients are
reduced over ``fsdp``, and the residual stream is all-reduced over ``tp``
after each row-parallel product (``wo``, ``w_down``: Megatron's two a layer
a pass, at one data shard's rows). GSPMD arrives there only because
``models.llama.forward_hidden`` STATES the stream's layout
(``activation_sharding``: batch over the data axes) when its attention is
bound to a mesh. The parameters' specs alone do not: the embedding is
``P("tp", "fsdp")``, its lookup comes out with ``d_model`` split over
``fsdp``, and the partitioner then keeps the stream so, as if ``fsdp`` were
a second tensor axis. Compiled for a ``v5e:2x2`` at Mistral-7B's widths,
``fsdp=2, tp=2``, 2 x 4096 tokens (AOT, PR 53; operations and bytes of
their results a device a step, unpinned -> pinned): 390 -> 2 all-to-alls
(23.99 -> 0.07 GB; the two left move the embedding's rows, forward and
backward), all-reduces 26.39 -> 12.61 GB and none at the whole batch any
more, all-gathers 62.20 -> 47.24 GB and weights only.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# Transformer sharding rules, megatron convention:
#   attn qkv:   (d_model, heads*head_dim)   -> col-parallel: shard axis 1 on tp
#   attn out:   (heads*head_dim, d_model)   -> row-parallel: shard axis 0 on tp
#   mlp up/gate:(d_model, d_ff)             -> col-parallel
#   mlp down:   (d_ff, d_model)             -> row-parallel
# fsdp shards the *other* big axis (ZeRO-3).
LLAMA_RULES: Tuple[Tuple[str, P], ...] = (
    (r".*embedding$", P("tp", "fsdp")),
    (r".*(wq|wk|wv|w_qkv)$", P("fsdp", "tp")),
    (r".*wo$", P("tp", "fsdp")),
    (r".*(w_gate|w_up)$", P("fsdp", "tp")),
    (r".*w_down$", P("tp", "fsdp")),
    (r".*lm_head$", P("fsdp", "tp")),
    (r".*(norm|scale|bias)$", P()),
    (r".*", P()),
)


# ViT family (models/vit.py): same megatron convention — qkv/up
# col-parallel on tp, out/down row-parallel; patch embed col-parallel;
# pos/cls/norms replicated; classifier head col-parallel.
# NOTE: tree paths are '/'-joined (see _tree_paths), not '.'-joined.
VIT_RULES: Tuple[Tuple[str, P], ...] = (
    (r".*patch_embed/w$", P("fsdp", "tp")),
    (r".*(wq|wk|wv)$", P("fsdp", "tp")),
    (r".*wo$", P("tp", "fsdp")),
    (r".*w_up$", P("fsdp", "tp")),
    (r".*w_down$", P("tp", "fsdp")),
    (r".*head/w$", P("fsdp", "tp")),
    (r".*(pos_embed|cls_token|norm|scale|bias|/b)$", P()),
    (r".*", P()),
)


def spec_for(path: str, rules: Sequence[Tuple[str, P]] = LLAMA_RULES) -> P:
    for pattern, spec in rules:
        if re.fullmatch(pattern, path):
            return spec
    return P()


def _tree_paths(tree: PyTree) -> PyTree:
    """Mirror tree with '/'-joined string paths at the leaves."""

    def path_str(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        return "/".join(parts)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [path_str(path) for path, _ in flat])


def clean_spec(spec: P, dims: Sequence[int], mesh: Mesh) -> P:
    """Drop spec axes that don't divide the corresponding dimension."""
    cleaned = []
    for i, axis in enumerate(spec):
        if axis is None or i >= len(dims):
            cleaned.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        cleaned.append(axis if dims[i] % size == 0 else None)
    while cleaned and cleaned[-1] is None:
        cleaned.pop()
    return P(*cleaned)


def shardings_for_tree(tree: PyTree, mesh: Mesh,
                       rules: Sequence[Tuple[str, P]] = LLAMA_RULES) -> PyTree:
    """PartitionSpec tree for a parameter pytree by name patterns.

    Specs referencing mesh axes of size 1 are harmless (XLA treats them as
    unsharded), so one rule set serves every MeshSpec.
    """
    paths = _tree_paths(tree)

    def leaf_sharding(path: str, leaf) -> NamedSharding:
        spec = spec_for(path, rules)
        dims = getattr(leaf, "shape", ())
        return NamedSharding(mesh, clean_spec(spec, dims, mesh))

    return jax.tree.map(leaf_sharding, paths, tree)


def stage_submesh(n_devices: int,
                  devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """An fsdp-only mesh for ONE pipeline stage (pp×fsdp topology: the
    pp axis lives BETWEEN programs — each stage is its own XLA program
    on its own slice — so the per-stage mesh carries only the intra-
    slice axis). The same LLAMA_RULES serve a stage param subtree
    unchanged: stage trees keep the ``layers/<i>/wq`` path shapes the
    rules match on."""
    from .mesh import MeshSpec, make_mesh

    if devices is None:
        devices = jax.devices()[:n_devices]
    return make_mesh(MeshSpec(fsdp=n_devices), devices)


def activation_sharding(mesh: Mesh) -> NamedSharding:
    """The residual stream's and its cotangent's sharding ``[B, L, D]``:
    batch over the data-like axes, seq/d whole. ``llama.forward_hidden``
    pins the stream to it under a mesh (module docstring); between pipeline
    stages the DCN boundary ships per-chip rows — no resharding at the
    hop."""
    return NamedSharding(mesh, P(("dp", "fsdp", "ep"), None, None))


def optimizer_shardings(abstract_params: PyTree, param_shardings: PyTree,
                        abstract_opt: PyTree, mesh: Mesh) -> PyTree:
    """ShapeDtypeStruct tree for an optimizer state whose moments mirror
    their parameter's sharding. Relies on optax's structure-preserving
    ``opt.init`` (mu/nu subtrees repeat the param tree, so a param's
    keypath is a suffix of its moment's keypath); scalars like ``count``
    are replicated. Shared by the fsdp=64 and per-stage (pp×fsdp) AOT
    certification paths in ``benchmarks/certify_8b.py``."""
    from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

    pflat, _ = tree_flatten_with_path(abstract_params)
    pmap = list(zip((keystr(kp) for kp, _ in pflat),
                    jax.tree.leaves(param_shardings)))
    oflat, otreedef = tree_flatten_with_path(abstract_opt)
    oleaves = []
    for kp, leaf in oflat:
        ks = keystr(kp)
        sh = next((s for ppath, s in pmap if ks.endswith(ppath)),
                  NamedSharding(mesh, P()))
        oleaves.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sh))
    return tree_unflatten(otreedef, oleaves)


def apply_shardings(tree: PyTree, shardings: PyTree) -> PyTree:
    """Device-put a host pytree onto its shardings (initial placement)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), tree, shardings)


def constrain(tree: PyTree, shardings: PyTree) -> PyTree:
    """In-jit sharding constraints (GSPMD hints)."""
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, shardings)
