"""Train-step construction: loss, grads, microbatching, optimizer fusion.

``make_train_step`` returns a pure ``(state, batch) -> (state, metrics)``
suitable for jit/pjit; the dry-run lowers exactly this function for every
architecture's ``train_4k`` cell.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist import compress
from repro.dist import sharding as shd
from repro.dist.pipeline import get_schedule
from repro.models import encdec, lm
from repro.nn.core import ParamSpec
from repro.optim.adamw import AdamState, Optimizer, apply_updates
from repro.utils.tree import global_norm


class TrainState(NamedTuple):
    params: Dict
    opt_state: object
    step: jnp.ndarray
    # error-feedback residuals for the compressed pod-axis gradient
    # reduction (None outside the multi-pod shard_map step).  Leaves carry
    # a leading pod-block dim: global (pod, *param_shape), sharded P("pod")
    # — the residual is *local* to a pod rank by construction.
    ef: object = None


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL in f32.  logits (B, S, V); labels (B, S) int32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


def loss_fn(params: Dict, batch: Dict, cfg: ModelConfig,
            mesh=None) -> Tuple[jax.Array, Dict]:
    if cfg.family == "encdec":
        logits = encdec.forward(params, batch["frames"], batch["tokens"],
                                cfg, mesh=mesh)
    else:
        logits = lm.forward(params, batch["tokens"], cfg, mesh=mesh)
    loss = cross_entropy(logits, batch["labels"])
    return loss, {"loss": loss}


def _model_of(cfg: ModelConfig):
    return encdec if cfg.family == "encdec" else lm


def train_state_specs(cfg: ModelConfig, mesh, *, ef: bool = False
                      ) -> "TrainState":
    """PartitionSpec tree of the TrainState the train step on ``mesh``
    takes.  A ``pipe`` axis >= 2 selects the shard_map pipeline step's
    layout (:func:`repro.dist.sharding.sharded_param_specs`, plus the
    error-feedback residuals' specs when ``ef``); any other mesh the dist
    layer's logical-axis layout (FSDP over ``data`` x TP over ``model``).
    Optimizer moments mirror the parameters; counters are replicated."""
    spec_tree = _model_of(cfg).model_spec(cfg)
    if shd.pipe_size(mesh) > 1:
        p_specs = shd.sharded_param_specs(spec_tree, shd.STAGE_KEYS, mesh)
    else:
        p_specs = shd.logical_specs(spec_tree, mesh)
    ef_specs = (shd.sharded_ef_specs(spec_tree, shd.STAGE_KEYS, mesh)
                if ef else None)
    return TrainState(params=p_specs,
                      opt_state=AdamState(step=P(), mu=p_specs, nu=p_specs),
                      step=P(), ef=ef_specs)


def decay_mask(cfg: ModelConfig):
    """AdamW's weight-decay mask for the model's tree: matrices only, a
    leaf of a stacked layer group being a matrix when each layer's slice
    is (its axes other than ``layers``); norm scales and biases are left
    out, stacked or not."""
    spec_tree = _model_of(cfg).model_spec(cfg)
    mask = jax.tree.map(
        lambda s: sum(a != "layers" for a in s.axes) >= 2, spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))
    return lambda params: mask


def init_train_state(cfg: ModelConfig, opt: Optimizer, key,
                     mesh=None, params: Optional[Dict] = None) -> TrainState:
    """A fresh TrainState.  With ``mesh``, every leaf is created in place
    under :func:`train_state_specs` -- the layout the step on ``mesh``
    takes -- and never whole on one device first: at published widths the
    parameters and moments alone outgrow one chip.  The pipeline step's
    error-feedback residuals come along when its compressed pod reduction
    is on (:func:`wants_ef`).  ``params`` (the model's tree, anywhere)
    replaces the random initialization from ``key``: it is placed in the
    same layout, and the optimizer moments start at zero."""
    model = _model_of(cfg)

    def init(params):
        if params is None:
            params = model.init_model(cfg, key)
        return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    if mesh is None:
        return init(params)
    specs = train_state_specs(cfg, mesh)
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    if params is not None:
        params = jax.device_put(params, shardings.params)
    state = jax.jit(init, out_shardings=shardings, donate_argnums=0)(params)
    if wants_ef(cfg, mesh):
        state = state._replace(ef=init_ef_state(
            state.params, mesh, spec_tree=model.model_spec(cfg)))
    return state


def make_train_step(cfg: ModelConfig, opt: Optimizer, mesh=None,
                    num_microbatches: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    ``num_microbatches > 1`` accumulates gradients over sequential
    microbatches (lax.scan) — the standard memory/batch-size lever.

    With ``mesh`` given, the step is fully sharded by the dist layer:
    params (and thus grads / optimizer moments) follow the logical-axis
    rules (FSDP over ``data`` × TP over ``model``), the batch follows
    ``batch_spec``, and XLA's SPMD partitioner inserts the collectives."""

    param_sh = batch_of = None
    if mesh is not None:
        param_sh = shd.param_shardings(_model_of(cfg).model_spec(cfg), mesh)

        def batch_of(batch):
            return jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, jax.sharding.NamedSharding(
                        mesh, shd.batch_spec(mesh, x.shape[0], ndim=x.ndim))),
                batch)

    grad_fn = jax.value_and_grad(
        functools.partial(loss_fn, cfg=cfg, mesh=mesh), has_aux=True)

    def compute_grads(params, batch):
        if num_microbatches == 1:
            (loss, aux), grads = grad_fn(params, batch)
            return loss, grads

        def split(x):
            return x.reshape((num_microbatches,
                              x.shape[0] // num_microbatches) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            loss_acc, grads_acc = carry
            (loss, _), grads = grad_fn(params, mb)
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            return (loss_acc + loss, grads_acc), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads_sum), _ = jax.lax.scan(body, (jnp.zeros(()), zeros),
                                                micro)
        scale = 1.0 / num_microbatches
        return loss_sum * scale, jax.tree.map(lambda g: g * scale, grads_sum)

    def train_step(state: TrainState, batch: Dict):
        if param_sh is not None:
            state = state._replace(
                params=jax.lax.with_sharding_constraint(state.params,
                                                        param_sh))
            batch = batch_of(batch)
        loss, grads = compute_grads(state.params, batch)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        metrics = {
            "loss": loss,
            "grad_norm": global_norm(grads),
            "step": state.step + 1,
        }
        return TrainState(params, opt_state, state.step + 1), metrics

    return train_step


# ---------------------------------------------------------------------------
# shard_map distributed step: gpipe over `pipe`, compressed psum over `pod`
# ---------------------------------------------------------------------------

class PipelineStepError(ValueError):
    """A config/mesh combination the shard_map pipeline step cannot stage
    (raised eagerly by :func:`make_sharded_train_step`'s validation).
    Callers offering a GSPMD fallback catch exactly this — not bare
    ValueError — so genuine construction bugs still surface."""


def wants_ef(cfg: ModelConfig, mesh) -> bool:
    """True when the sharded step on ``mesh`` will carry error-feedback
    state (compressed pod-axis reduction active)."""
    return (cfg.compress_pod_grads and shd.pipe_size(mesh) > 1
            and shd.axis_sizes(mesh).get("pod", 1) > 1)


def init_ef_state(params, mesh, spec_tree=None):
    """Zero error-feedback residuals for :func:`make_sharded_train_step`:
    one f32 block per ``pod`` rank, stacked on a leading dim.  Each leaf is
    created directly under its shard_map sharding (P("pod") / stage leaves
    P("pod", "pipe")) — materializing (pod, *param_shape) zeros replicated
    on the default device would double the fp32 parameter footprint per
    pod before the step ever runs.

    ``spec_tree`` (the model's ParamSpec tree) is required when ``mesh``
    carries a ``model`` axis > 1: the residuals then mirror the
    tensor-parallel weight shards, which takes the logical axes."""
    pod = shd.axis_sizes(mesh).get("pod", 1)
    ef_specs = shd.sharded_ef_specs(
        spec_tree if spec_tree is not None else params, mesh=mesh)

    def make(p, spec):
        sharding = jax.sharding.NamedSharding(mesh, spec)
        return jax.jit(
            lambda: jnp.zeros((pod,) + p.shape, jnp.float32),
            out_shardings=sharding)()

    return jax.tree.map(make, params, ef_specs)


def make_sharded_train_step(cfg: ModelConfig, opt: Optimizer, mesh, *,
                            num_microbatches: Optional[int] = None,
                            compress_pod: Optional[bool] = None,
                            schedule=None,
                            overlap_pod_reduce: Optional[bool] = None):
    """Explicit-collective train step built on ``jax.shard_map``.

    Per device, the step: embeds the local batch shard, stages the decoder
    blocks through a :class:`repro.dist.pipeline.PipelineSchedule`
    (``schedule`` / ``cfg.pipeline_schedule``: ``"gpipe"`` or ``"1f1b"``)
    microbatched over the ``pipe`` axis (each rank owns ``n_layers / pipe``
    contiguous layers — stage weights never replicate), differentiates the
    pipeline in place (the ring ppermute transposes to the backward ring),
    then reduces gradients: glue params (embed / final norm / head) psum
    over ``pipe``, everything pmean over ``data``, and over the slow
    ``pod`` axis either :func:`repro.dist.compress.compressed_psum` (bf16
    wire format + error feedback, ``compress_pod``) or a plain fp32 pmean.
    With ``overlap_pod_reduce`` (default ``cfg.overlap_pod_reduce``) the
    compressed reduction is issued per gradient group — stage grads first,
    as they finalize during the backward drain — and joined only at the
    optimizer update, so the scheduler can overlap the slow pod wire time
    with the remaining backward work and the next step's fill phase.

    A ``model`` mesh axis > 1 composes tensor parallelism into the stage
    bodies: attention/MLP weights shard per head/column over ``model``
    (:func:`repro.dist.sharding.sharded_param_specs`), the blocks psum
    their partial projections in-stage (``repro.nn`` ``tp_axis`` paths),
    and glue stays replicated.  Supported for the dense family with
    ``d_ff`` / ``n_heads`` / ``n_kv_heads`` divisible by the axis size.

    Remaining constraints (checked eagerly): ``pipe >= 2`` on the mesh;
    family in dense/moe/ssm with a uniform layer stack divisible by
    ``pipe``; ``opt`` from :mod:`repro.optim.adamw` (AdamState-shaped
    state).

    Returns ``train_step(state, batch) -> (state, metrics)`` with the same
    contract as :func:`make_train_step`; ``state.ef`` must be
    :func:`init_ef_state` when the compressed path is active, else None.
    """
    sizes = shd.axis_sizes(mesh)
    n_stages = sizes.get("pipe", 1)
    tp = sizes.get("model", 1)
    if n_stages < 2:
        raise PipelineStepError("make_sharded_train_step needs a mesh 'pipe' axis "
                         f"of size >= 2, got {sizes}")
    if cfg.family not in ("dense", "moe", "ssm"):
        raise PipelineStepError(f"pipeline step: unsupported family {cfg.family}")
    if cfg.family == "moe" and cfg.first_dense_layers:
        raise PipelineStepError("pipeline step: moe configs with leading dense "
                         "layers are not stage-uniform")
    if cfg.n_layers % n_stages:
        raise PipelineStepError(f"n_layers={cfg.n_layers} not divisible by "
                         f"pipe={n_stages}")
    if tp > 1:
        if cfg.family != "dense":
            raise PipelineStepError(
                "tensor-parallel stage composition (model axis > 1) "
                f"supports the dense family only, got {cfg.family}")
        if cfg.mla:
            raise PipelineStepError("pipeline step: MLA attention has no "
                                    "explicit-TP path")
        if cfg.qk_norm:
            raise PipelineStepError(
                "pipeline step: qk_norm scales live inside the TP region "
                "and would need a model-axis grad reduction")
        for val, nm in ((cfg.d_ff, "d_ff"), (cfg.n_heads, "n_heads"),
                       (cfg.n_kv_heads, "n_kv_heads")):
            if val % tp:
                raise PipelineStepError(
                    f"{nm}={val} not divisible by model={tp} (head-/column-"
                    "granular TP sharding)")
    try:
        sched = get_schedule(schedule if schedule is not None
                             else cfg.pipeline_schedule)
    except ValueError as e:
        raise PipelineStepError(str(e)) from None
    tp_axis = "model" if tp > 1 else None
    n_micro = num_microbatches or cfg.pipeline_microbatches
    has_pod = sizes.get("pod", 1) > 1
    if compress_pod is None:
        compress_pod = cfg.compress_pod_grads
    compress_pod = bool(compress_pod and has_pod)
    if overlap_pod_reduce is None:
        overlap_pod_reduce = cfg.overlap_pod_reduce
    dp_total = sizes.get("pod", 1) * sizes.get("data", 1)
    stage_keys = tuple(k for k in shd.STAGE_KEYS)
    layers_per_stage = cfg.n_layers // n_stages
    windows_full = (jnp.asarray(lm.window_schedule(cfg))
                    if cfg.family in ("dense", "moe") else None)

    def local_loss(params, batch):
        tokens = batch["tokens"]
        x = lm.embed_forward(params, tokens, cfg)
        mb = tokens.shape[0] // n_micro
        micro = x.reshape((n_micro, mb) + x.shape[1:])
        if windows_full is not None:
            stage = jax.lax.axis_index("pipe")
            wloc = jax.lax.dynamic_slice_in_dim(
                windows_full, stage * layers_per_stage, layers_per_stage)
        else:
            wloc = None

        def stage_fn(w, h):
            return lm.stage_forward(cfg, w, h, windows=wloc,
                                    tp_axis=tp_axis)

        y = sched.run_local(stage_fn, params["layers"], micro,
                            n_stages=n_stages, axis="pipe",
                            replicate_out=False)
        y = y.reshape((tokens.shape[0],) + y.shape[2:])
        logits = lm.head_forward(params, y, cfg)
        nll = cross_entropy(logits, batch["labels"])
        # only the last pipe rank holds real pipeline outputs; masking the
        # loss there makes the summed-over-ranks scalar equal ONE copy of
        # the shard loss, so backward collectives don't over-count it.
        # Under TP every model rank replicates the final stream, so the
        # loss is additionally owned by model rank 0 alone — same trick,
        # second axis.
        owns = jax.lax.axis_index("pipe") == n_stages - 1
        if tp > 1:
            owns = owns & (jax.lax.axis_index("model") == 0)
        return jnp.where(owns, nll, 0.0)

    # --- in/out specs + per-leaf reduction plan ----------------------------
    state_specs = train_state_specs(cfg, mesh, ef=compress_pod)
    p_specs = state_specs.params

    # per-leaf reduction plans, read straight off the specs.  Gradients are
    # *partial* over every pipeline/TP axis the leaf is NOT sharded on
    # (the masked loss is owned by one (pipe, model) rank; each rank's
    # backward carries only its own compute's contribution), so assembly
    # psums over {pipe, model} minus the leaf's sharded axes — for the
    # model=1 mesh this degenerates to the classic glue-psum-over-pipe.
    # The global grad norm is the mirror image: leaves sharded over
    # pipe/model psum their squared sums over exactly those axes.
    def _spec_axes(sp) -> tuple:
        ents = []
        for e in tuple(sp):
            ents.extend(e if isinstance(e, (tuple, list)) else (e,))
        return tuple(a for a in ("pipe", "model") if a in ents)

    def is_spec(x):
        return isinstance(x, P)

    partial_axes = ("pipe", "model") if tp > 1 else ("pipe",)
    flat_specs = [_spec_axes(sp)
                  for sp in jax.tree.leaves(p_specs, is_leaf=is_spec)]
    flat_norm_axes = flat_specs
    flat_psum_axes = [tuple(a for a in partial_axes if a not in sharded)
                      for sharded in flat_specs]

    def assemble_grads(grads):
        flat, tdef = jax.tree.flatten(grads)
        flat = [jax.lax.psum(g, ax) if ax else g
                for g, ax in zip(flat, flat_psum_axes)]
        return jax.tree.unflatten(tdef, flat)

    def global_sq(grads):
        groups: Dict[tuple, list] = {}
        for g, ax in zip(jax.tree.leaves(grads), flat_norm_axes):
            groups.setdefault(ax, []).append(
                jnp.sum(jnp.square(g.astype(jnp.float32))))
        total = jnp.zeros(())
        for ax, parts in groups.items():
            part = jnp.sum(jnp.stack(parts))
            total = total + (jax.lax.psum(part, ax) if ax else part)
        return total

    def device_step(state: TrainState, batch: Dict):
        params = state.params
        loss_part, grads = jax.value_and_grad(local_loss)(params, batch)
        grads = assemble_grads(grads)
        loss = jax.lax.psum(loss_part, partial_axes)
        if "data" in sizes:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
            loss = jax.lax.pmean(loss, "data")
        ef = state.ef
        if has_pod:
            loss = jax.lax.pmean(loss, "pod")
            if compress_pod:
                err = jax.tree.map(lambda e: e[0], ef)
                if overlap_pod_reduce:
                    # issue per-group reductions, stage grads first: their
                    # buckets finalize during the backward drain and can
                    # fly while glue backward / metrics still compute —
                    # joined only at the optimizer update below
                    order = ([k for k in grads if k in stage_keys]
                             + [k for k in grads if k not in stage_keys])
                    grads, new_err = compress.compressed_psum_grouped(
                        grads, err, "pod", order)
                else:
                    grads, new_err = compress.compressed_psum(grads, err,
                                                              "pod")
                ef = jax.tree.map(lambda e: e[None], new_err)
            else:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"),
                                     grads)
        # true global grad norm from the per-leaf reduction plan
        gnorm = jnp.sqrt(global_sq(grads))
        if opt.max_grad_norm is not None:
            # clip against the GLOBAL norm here; after this scaling every
            # per-rank norm opt.update can see is <= max_grad_norm, so its
            # own (local) clip is a no-op — clipping happens exactly once
            scale = jnp.minimum(1.0, opt.max_grad_norm / (gnorm + 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        updates, opt_state = opt.update(grads, state.opt_state, params)
        params = apply_updates(params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": state.step + 1}
        return TrainState(params, opt_state, state.step + 1, ef), metrics
    metric_specs = {"loss": P(), "grad_norm": P(), "step": P()}
    bspec = P(shd.dp_axes(mesh))

    def train_step(state: TrainState, batch: Dict):
        batch_size = batch["tokens"].shape[0]
        if batch_size % dp_total:
            raise ValueError(f"global batch {batch_size} not divisible by "
                             f"pod*data={dp_total}")
        if (batch_size // dp_total) % n_micro:
            raise ValueError(f"local batch {batch_size // dp_total} not "
                             f"divisible by {n_micro} microbatches")
        if compress_pod and state.ef is None:
            raise ValueError("compressed pod reduction needs state.ef — "
                             "initialize it with init_ef_state(params, mesh)")
        batch_specs = jax.tree.map(lambda _: bspec, batch)
        fn = jax.shard_map(device_step, mesh=mesh,
                           in_specs=(state_specs, batch_specs),
                           out_specs=(state_specs, metric_specs),
                           check_vma=False)
        return fn(state, batch)

    return train_step


def make_eval_step(cfg: ModelConfig, mesh=None):
    def eval_step(params: Dict, batch: Dict):
        loss, _ = loss_fn(params, batch, cfg, mesh)
        return {"loss": loss}
    return eval_step
