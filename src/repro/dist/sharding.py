"""Logical-axis sharding rules: the single place that maps *logical* axis
names (on :class:`repro.nn.core.ParamSpec` leaves) and runtime tensors onto
*mesh* axes.

Mesh axes (see ``repro.launch.mesh``):

  ``pod``    — outermost data-parallel replica groups (multi-pod runs);
  ``data``   — within-pod data parallelism (batch, FSDP weight shards);
  ``model``  — tensor / expert parallelism;
  ``pipe``   — pipeline stages (``repro.dist.pipeline``).

Every rule degrades by *divisibility fallback*: a dimension that is not
divisible by its target mesh axis (or whose target axis is absent) is
replicated instead — the layer never produces an unlowerable spec, so the
same model code runs on a laptop mesh and the 512-chip production mesh.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis -> mesh axes to try, in preference order.  ``embed`` (the
# contraction dim of every matmul) shards over ``data`` — classic FSDP: the
# SPMD partitioner turns it into per-step all-gathers instead of resident
# replicas.  ``mlp``/``heads``/``vocab``/``experts`` shard over ``model``
# (tensor/expert parallelism).  ``layers`` is the scan dimension and stays
# replicated.
LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "embed": ("data",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "layers": (),
}


# ---------------------------------------------------------------------------
# mesh introspection (works on Mesh and AbstractMesh alike)
# ---------------------------------------------------------------------------

def axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: size} for a concrete or abstract mesh."""
    return dict(mesh.shape)


def dp_axes(mesh):
    """The data-parallel mesh axes present on ``mesh``: ``("pod", "data")``,
    ``"data"``, or None.  Usable directly inside a PartitionSpec."""
    present = tuple(a for a in ("pod", "data") if a in axis_sizes(mesh))
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def dp_size(mesh) -> int:
    sizes = axis_sizes(mesh)
    return sizes.get("pod", 1) * sizes.get("data", 1)


def model_size(mesh) -> int:
    return axis_sizes(mesh).get("model", 1)


def pipe_size(mesh) -> int:
    return axis_sizes(mesh).get("pipe", 1)


# ---------------------------------------------------------------------------
# parameter shardings
# ---------------------------------------------------------------------------

def spec_for_axes(axes: Sequence[Optional[str]], mesh,
                  shape: Optional[Sequence[int]] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec on ``mesh``.

    A dimension is sharded over the first mesh axis in its rule that (a)
    exists on the mesh, (b) is not already used by an earlier dimension,
    and (c) divides the dimension size (when ``shape`` is given);
    otherwise it is replicated.
    """
    sizes = axis_sizes(mesh)
    used = set()
    out = []
    for i, name in enumerate(axes):
        choice = None
        for mesh_ax in LOGICAL_RULES.get(name, ()):
            n = sizes.get(mesh_ax)
            if n is None or mesh_ax in used:
                continue
            if shape is not None and shape[i] % n != 0:
                continue
            choice = mesh_ax
            used.add(mesh_ax)
            break
        out.append(choice)
    return P(*out)


def _is_param_spec(x) -> bool:
    # duck-typed so this module never imports repro.nn (no import cycles)
    return hasattr(x, "axes") and hasattr(x, "shape")


def param_shardings(spec_tree, mesh):
    """ParamSpec tree -> NamedSharding tree (same structure as the params
    ``init_params`` builds from the same spec tree)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_for_axes(s.axes, mesh, s.shape)),
        spec_tree, is_leaf=_is_param_spec)


def logical_specs(spec_tree, mesh):
    """Like :func:`param_shardings` but returning bare PartitionSpecs —
    usable with AbstractMesh (no devices) and as shard_map in/out specs."""
    return jax.tree.map(
        lambda s: spec_for_axes(s.axes, mesh, s.shape),
        spec_tree, is_leaf=_is_param_spec)


# ---------------------------------------------------------------------------
# shard_map pipeline-step specs (train.step.make_sharded_train_step)
# ---------------------------------------------------------------------------

# top-level parameter-tree keys whose leaves are stacked per-layer weights
# (leading dim = n_layers) that the pipeline step splits one block of
# contiguous layers per ``pipe`` rank.  Everything else is "glue" (embed,
# final norm, lm head) and stays replicated across the pipe axis.
STAGE_KEYS: Tuple[str, ...] = ("layers",)

# logical axes the explicit-TP pipeline step shards over ``model``: the
# attention head / MLP column dims whose partial projections the stage body
# reassembles with an in-stage psum (``repro.nn`` ``tp_axis`` paths).  No
# divisibility fallback here — ``make_sharded_train_step`` validates the
# dims eagerly, because a silently replicated leaf would make the stage's
# unconditional psum double-count.
TP_STAGE_AXES: Tuple[str, ...] = ("mlp", "heads", "kv_heads")


def _stage_leaf_spec(leaf, tp: bool) -> P:
    if not tp:
        return P("pipe")
    if not _is_param_spec(leaf):
        raise TypeError(
            "sharded_param_specs needs a ParamSpec tree (logical axes) to "
            "compose pipe with tensor parallelism; got a bare array leaf")
    parts = ["pipe" if i == 0 and ax == "layers"
             else ("model" if ax in TP_STAGE_AXES else None)
             for i, ax in enumerate(leaf.axes)]
    return P(*parts)


def sharded_param_specs(params_tree, stage_keys: Sequence[str] = STAGE_KEYS,
                        mesh=None):
    """PartitionSpec tree for the shard_map train step's parameters: stacked
    per-layer leaves shard their leading (layer) dim over ``pipe``; glue
    parameters are replicated across ``pipe`` (and ``model``).  When
    ``mesh`` carries a ``model`` axis of size > 1, stage leaves additionally
    shard their :data:`TP_STAGE_AXES` dims over ``model`` — the weight
    layout of the TP-composable stage bodies.  Accepts a params tree or a
    ParamSpec tree (the latter is required for TP, which needs the logical
    axes)."""
    tp = mesh is not None and model_size(mesh) > 1

    def sub(key, tree):
        if key in stage_keys:
            return jax.tree.map(lambda s: _stage_leaf_spec(s, tp), tree,
                                is_leaf=_is_param_spec)
        return jax.tree.map(lambda _: P(), tree, is_leaf=_is_param_spec)
    return {k: sub(k, v) for k, v in params_tree.items()}


def sharded_ef_specs(params_tree, stage_keys: Sequence[str] = STAGE_KEYS,
                     mesh=None):
    """PartitionSpec tree for the compressed-psum error-feedback residuals:
    each leaf is its parameter's spec (:func:`sharded_param_specs`) with a
    leading ``pod``-block dim prepended — the residual is local to a pod
    rank, and mirrors the parameter/gradient sharding underneath."""
    p_specs = sharded_param_specs(params_tree, stage_keys, mesh)
    return jax.tree.map(lambda sp: P("pod", *sp), p_specs)


# ---------------------------------------------------------------------------
# activation / batch shardings
# ---------------------------------------------------------------------------

def batch_spec(mesh, batch: int, ndim: int = 2) -> P:
    """Leading-dim data parallelism with divisibility fallback."""
    dp = dp_axes(mesh)
    if dp is None or batch % dp_size(mesh) != 0:
        dp = None
    return P(dp, *([None] * (ndim - 1)))


def cache_sharding(mesh, batch: int, seq: int, n_kv_heads: int) -> P:
    """PartitionSpec for a (batch, seq, kv_heads, head_dim) KV cache.

    Heuristics, in order:
      * batch not data-divisible (the batch=1 long-context cell): shard the
        *sequence* over every divisible mesh axis — the cache dominates
        memory at 500k context, so it must spread over the whole slice;
      * kv heads divisible by ``model``: head sharding (dense GQA/MHA) —
        decode attention then needs no cross-device traffic at all;
      * MQA / few-kv-head models: sequence sharding over ``model`` (the
        flash-decode split-S pattern; partial softmax combines are cheap);
      * otherwise replicate the non-batch dims.
    """
    sizes = axis_sizes(mesh)
    model = sizes.get("model", 1)
    b_ax = dp_axes(mesh)
    if b_ax is not None and batch % dp_size(mesh) == 0:
        if n_kv_heads % model == 0 and model > 1:
            return P(b_ax, None, "model", None)
        if model > 1 and seq % model == 0:
            return P(b_ax, "model", None, None)
        return P(b_ax, None, None, None)

    # batch not shardable: spread the sequence as widely as divisibility
    # allows (prefer the full data×model slice, fall back to model only)
    for axes in (tuple(a for a in ("pod", "data", "model") if a in sizes),
                 tuple(a for a in ("data", "model") if a in sizes),
                 ("model",) if "model" in sizes else ()):
        if not axes:
            continue
        n = math.prod(sizes[a] for a in axes)
        if n > 1 and seq % n == 0:
            return P(None, axes, None, None)
    return P(None, None, None, None)


def decode_cache_shardings(cfg, caches, mesh):
    """NamedSharding tree for a decode-cache pytree (any model family).

    ``caches`` may hold arrays or ShapeDtypeStructs; leaves are classified
    by rank/shape the same way ``serve.decode.init_caches`` builds them.
    """
    def leaf_spec(x) -> P:
        shape = x.shape
        dp = dp_axes(mesh)
        b_ax = dp if shape[0] % dp_size(mesh) == 0 else None
        if len(shape) == 4 and shape[2] == cfg.n_kv_heads \
                and shape[3] == cfg.head_dim:
            return cache_sharding(mesh, shape[0], shape[1], cfg.n_kv_heads)
        if len(shape) == 4:  # ssm state (B, H, P, N)
            h_ax = "model" if shape[1] % model_size(mesh) == 0 else None
            return P(b_ax, h_ax, None, None)
        if len(shape) == 3:  # mla latent (B, S, R) / ssm conv (B, W, C)
            # shard the sequence, NOT the latent dim: the attention einsums
            # contract over R, and a contraction-dim sharding makes the SPMD
            # partitioner all-gather the whole (f32-upcast) cache every
            # layer — measured at 16.8 GB/device/step on deepseek decode_32k
            # before this rule (EXPERIMENTS.md §Perf cell B).
            if shape[1] % model_size(mesh) == 0 \
                    and shape[1] >= model_size(mesh):
                return P(b_ax, "model", None)
            if cfg.mla and shape[2] in (cfg.kv_lora_rank, cfg.qk_rope_dim):
                return P(b_ax, None, None)   # latent IS the contraction dim
            last_ax = "model" if shape[2] % model_size(mesh) == 0 \
                and shape[2] >= model_size(mesh) else None
            return P(b_ax, None, last_ax)
        return P(*([None] * len(shape)))

    return jax.tree.map(lambda x: NamedSharding(mesh, leaf_spec(x)), caches)


def kv_pool_shardings(cfg, caches, mesh, kinds):
    """Placement for the serve engine's paged KV cache.

    ``kinds`` is ``serve.decode.paged_cache_kinds(cfg)``, one entry per
    cache in the list.  Block-major page leaves
    ``(num_blocks, block_size, ...)`` NEVER shard the block or in-block
    position dims — every lane gathers arbitrary physical blocks through
    its table, so a sharded block dim would turn each gather into an
    all-to-all.  K/V pages ``(num_blocks, block_size, KH * D)`` shard the
    merged trailing dim over the model axis when the KV heads divide it
    (contiguous chunks are then whole heads), else replicate.  MLA latent
    pages replicate their trailing dim (it is the attention contraction
    — same rule as :func:`decode_cache_shardings`).  ``"slot"`` entries
    (recurrent state rows, ``max_batch + 1`` of them with the scratch row)
    keep the decode-cache rules."""
    def paged_leaf_spec(x) -> P:
        shape = x.shape
        if not cfg.mla and len(shape) == 3 \
                and shape[2] == cfg.n_kv_heads * cfg.head_dim:
            h_ax = "model" if cfg.n_kv_heads % model_size(mesh) == 0 else None
            return P(None, None, h_ax)
        return P(*([None] * len(shape)))

    return [decode_cache_shardings(cfg, c, mesh) if kind == "slot"
            else jax.tree.map(
                lambda x: NamedSharding(mesh, paged_leaf_spec(x)), c)
            for c, kind in zip(caches, kinds)]
