"""ShapeDtypeStruct stand-ins + shardings for every (arch × shape) cell.

``lowerable(cfg, shape_name, mesh)`` returns (fn, args_sds) such that
``jax.jit(fn, in_shardings=...).lower(*args_sds)`` is exactly the cell the
dry-run and roofline analysis evaluate — no device allocation anywhere.

Kinds:
  train_4k     -> train_step(state, batch)
  prefill_32k  -> prefill(params, inputs) -> logits
  decode_32k / long_500k -> serve_step(params, caches, token, pos)
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig
from repro.dist import sharding as shd
from repro.sched.scenario import Scenario
from repro.models import encdec, lm
from repro.optim import adamw as adamw_fn, constant_schedule
from repro.serve import decode as serve_decode
from repro.train.step import (PipelineStepError, TrainState,
                              make_sharded_train_step, make_train_step,
                              wants_ef)


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _with_sharding(tree_sds, tree_sharding):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_sds, tree_sharding)


def params_sds(cfg: ModelConfig, mesh) -> Tuple[Dict, Dict]:
    """(ShapeDtypeStruct tree, NamedSharding tree) for the parameters."""
    model = encdec if cfg.family == "encdec" else lm
    sds = jax.eval_shape(functools.partial(model.init_model, cfg),
                         jax.random.PRNGKey(0))
    spec_tree = model.model_spec(cfg)
    shardings = shd.param_shardings(spec_tree, mesh)
    return _with_sharding(sds, shardings), shardings


def _batch_sds(cfg: ModelConfig, mesh, seq: int, batch: int,
               with_labels: bool = True) -> Dict:
    bspec = shd.batch_spec(mesh, batch)
    out = {"tokens": _sds((batch, seq), jnp.int32, mesh, bspec)}
    if with_labels:
        out["labels"] = _sds((batch, seq), jnp.int32, mesh, bspec)
    if cfg.frontend == "audio_frames":
        out["frames"] = _sds((batch, seq, cfg.d_model), jnp.bfloat16, mesh,
                             shd.batch_spec(mesh, batch, ndim=3))
    return out


def sharded_train_lowerable(cfg: ModelConfig, mesh, *, seq: int,
                            batch: int, num_microbatches: int = None):
    """(fn, args_sds) for the shard_map pipeline train step on ``mesh`` —
    the ``pipe``-axis analogue of the ``train`` branch of :func:`lowerable`
    (requires ``pipe >= 2``; a ``model`` axis > 1 composes tensor
    parallelism into the stage bodies — see
    ``train.step.make_sharded_train_step`` for the constraints)."""
    step_fn = make_sharded_train_step(cfg, _lower_opt(), mesh,
                                      num_microbatches=num_microbatches)
    spec_tree = lm.model_spec(cfg)
    p_sds = jax.eval_shape(functools.partial(lm.init_model, cfg),
                           jax.random.PRNGKey(0))
    p_specs = shd.sharded_param_specs(spec_tree, mesh=mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    p_sds = _with_sharding(p_sds, p_sh)
    opt_sds = jax.eval_shape(_lower_opt().init, p_sds)
    opt_sds = type(opt_sds)(
        step=_sds((), jnp.int32, mesh, P()),
        mu=jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), opt_sds.mu, p_sh),
        nu=jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), opt_sds.nu, p_sh))
    ef_sds = None
    if wants_ef(cfg, mesh):
        pod = shd.axis_sizes(mesh).get("pod", 1)
        ef_specs = shd.sharded_ef_specs(spec_tree, mesh=mesh)
        ef_sds = jax.tree.map(
            lambda s, sp: _sds((pod,) + s.shape, jnp.float32, mesh, sp),
            p_sds, ef_specs)
    state_sds = TrainState(params=p_sds, opt_state=opt_sds,
                           step=_sds((), jnp.int32, mesh, P()),
                           ef=ef_sds)
    bspec = P(shd.dp_axes(mesh))
    batch_sds = {"tokens": _sds((batch, seq), jnp.int32, mesh, bspec),
                 "labels": _sds((batch, seq), jnp.int32, mesh, bspec)}
    return step_fn, (state_sds, batch_sds)


def _lower_opt():
    return adamw_fn(constant_schedule(3e-4), weight_decay=0.1,
                    max_grad_norm=1.0)


def lowerable(cfg: ModelConfig, shape_name: str, mesh):
    """-> (fn, args_sds tuple).  ``jax.jit(fn).lower(*args_sds)``."""
    seq, batch, kind = SHAPES[shape_name]

    if kind == "train" and shd.pipe_size(mesh) > 1:
        try:
            return sharded_train_lowerable(cfg, mesh, seq=seq, batch=batch)
        except PipelineStepError:
            # arch/mesh not stage-uniform (encdec/hybrid families, leading
            # dense MoE layers, layers not divisible by pipe): the jit/GSPMD
            # step below still lowers — it simply ignores the pipe axis —
            # so an all-arch sweep over a pipe mesh keeps going
            pass

    if kind == "train":
        p_sds, p_sh = params_sds(cfg, mesh)
        opt = _lower_opt()
        opt_sds = jax.eval_shape(opt.init, p_sds)
        opt_sh = type(opt_sds)(
            step=NamedSharding(mesh, P()),
            mu=jax.tree.map(lambda s: s.sharding, p_sds),
            nu=jax.tree.map(lambda s: s.sharding, p_sds))
        state_sds = TrainState(
            params=p_sds,
            opt_state=_with_sharding(opt_sds, opt_sh),
            step=_sds((), jnp.int32, mesh, P()))
        batch_sds = _batch_sds(cfg, mesh, seq, batch)
        step_fn = make_train_step(cfg, opt, mesh=mesh,
                                  num_microbatches=cfg.train_microbatches)
        return step_fn, (state_sds, batch_sds)

    if kind == "prefill":
        p_sds, _ = params_sds(cfg, mesh)
        batch_sds = _batch_sds(cfg, mesh, seq, batch, with_labels=False)

        if cfg.family == "encdec":
            def prefill(params, batch):
                return encdec.forward(params, batch["frames"],
                                      batch["tokens"], cfg, mesh=mesh)
        else:
            def prefill(params, batch):
                return lm.forward(params, batch["tokens"], cfg, mesh=mesh)
        return prefill, (p_sds, batch_sds)

    # decode kinds: one new token against a cache of length `seq`, with
    # the weights in the serving form (one pytree per layer)
    p_sds = serve_decode.serving_params(params_sds(cfg, mesh)[0])
    caches_sds = jax.eval_shape(
        functools.partial(serve_decode.init_caches, cfg, batch, seq))
    caches_sds = _with_sharding(
        caches_sds, shd.decode_cache_shardings(cfg, caches_sds, mesh))
    token_sds = _sds((batch, 1), jnp.int32, mesh,
                     shd.batch_spec(mesh, batch))
    pos_sds = _sds((), jnp.int32, mesh, P())

    def serve_step(params, caches, token, pos):
        return serve_decode.decode_step(params, caches, token, pos, cfg,
                                        mesh=mesh)
    return serve_step, (p_sds, caches_sds, token_sds, pos_sds)


# ---------------------------------------------------------------------------
# schedule-optimizer fleet
# ---------------------------------------------------------------------------

def kernel_fleet_names(cfg: ModelConfig):
    """Registry names of the schedule-optimizable kernels this config's
    forward pass leans on (see :func:`kernel_fleet` for the scenario-
    annotated form the launchers consume)."""
    fleet = ["matmul_leakyrelu", "fused_ff"]
    if cfg.norm == "rmsnorm":
        fleet.append("rmsnorm")
    if cfg.family in ("ssm", "hybrid"):
        fleet.append("ssd")
    if cfg.family != "ssm":            # attention stacks
        fleet += ["flash_attention", "softmax", "bmm"]
    return fleet


def shape_scenario(cfg: ModelConfig, shape_name: str) -> Scenario:
    """The workload point a (config × shape) cell runs the kernels at.

    Train/prefill cells keep the core fully occupied; decode cells sit at
    half occupancy for large batches and low occupancy for the
    single-stream long-context shape (one token per step leaves most of
    the machine idle — a different best schedule than the saturated
    case)."""
    seq, batch, kind = SHAPES[shape_name]
    if kind in ("train", "prefill"):
        occ = "full"
    else:
        occ = "half" if batch >= 64 else "low"
    return Scenario(batch=batch, seq_len=seq, dtype=cfg.dtype, occupancy=occ)


def fleet_scenarios(cfg: ModelConfig):
    """Distinct workload points (one per scenario bucket) derived from the
    config's supported shapes, in shape order."""
    out, seen = [], set()
    for shape_name in cfg.supported_shapes:
        sc = shape_scenario(cfg, shape_name)
        if sc.bucket not in seen:
            seen.add(sc.bucket)
            out.append(sc)
    return out


def kernel_fleet(cfg: ModelConfig):
    """``(kernel, Scenario)`` pairs for every schedule-optimizable kernel
    this config's forward pass leans on, at every workload point its
    supported shapes imply — the fleet ``python -m repro.launch.optimize
    --arch`` feeds to ``OptimizationSession.optimize_many`` and the
    serving launcher resolves through the schedule cache (one tuned
    schedule per kernel × scenario bucket)."""
    return [(name, sc) for name in kernel_fleet_names(cfg)
            for sc in fleet_scenarios(cfg)]
