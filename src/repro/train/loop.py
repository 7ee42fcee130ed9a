"""Training loop: data -> jit'd step -> metrics/checkpoints, with the
fault-tolerance contract the brief requires:

  * checkpoint every ``ckpt_every`` steps (async, atomic commit);
  * restart-from-LATEST on construction — a killed job resumes bitwise
    (deterministic data keyed by step + exact state restore);
  * failure injection (``fail_at_step``) for the FT tests;
  * straggler watermarks: per-step wall time ring buffer + a hook that
    fires when a step exceeds ``straggler_factor``× the running median —
    on synchronous SPMD the mitigation is checkpoint + elastic remesh,
    and the elastic path is restore(shardings=new_mesh) (tested).
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.ckpt import checkpoint
from repro.configs.base import ModelConfig
from repro.data.pipeline import make_data
from repro.dist import sharding as shd
from repro.optim import adamw as adamw_fn, linear_warmup_cosine
from repro.train.step import (init_train_state, make_sharded_train_step,
                              make_train_step)


class InjectedFailure(RuntimeError):
    pass


def pipeline_microbatch_clamp(n_micro: int, global_batch: int, mesh):
    """``(clamped, per_shard_batch)``: the pipeline microbatch count the
    Trainer will actually stream — the requested count gcd-clamped to
    divide the per-shard batch.  One definition, shared by the Trainer
    (which applies it) and ``launch.train`` (which warns about it)."""
    local_b = max(1, global_batch // max(1, shd.dp_size(mesh)))
    return math.gcd(n_micro, local_b) or 1, local_b


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    lr: float = 3e-4
    warmup: int = 10
    weight_decay: float = 0.1
    ckpt_every: int = 20
    ckpt_dir: Optional[str] = None
    num_microbatches: int = 1
    seed: int = 0
    fail_at_step: Optional[int] = None        # failure injection (tests)
    straggler_factor: float = 3.0
    log_every: int = 10


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                 straggler_hook: Optional[Callable[[int, float], None]] = None):
        self.cfg, self.tcfg, self.mesh = cfg, tcfg, mesh
        self.data = make_data(cfg, tcfg.seq_len, tcfg.global_batch, tcfg.seed)
        sched = linear_warmup_cosine(tcfg.lr, tcfg.warmup, tcfg.steps)
        self.opt = adamw_fn(sched, weight_decay=tcfg.weight_decay,
                               max_grad_norm=1.0)
        self.straggler_hook = straggler_hook
        self.step_times: List[float] = []
        self.metrics_log: List[Dict] = []
        self._ckpt = checkpoint.AsyncCheckpointer()

        # a mesh with a pipe axis >= 2 selects the shard_map pipeline step:
        # gpipe microbatches over `pipe`, compressed psum over `pod` (the
        # config opts in via pipeline_stages / compress_pod_grads — see
        # repro.launch.train, which sizes the mesh from them).  The state
        # is born in that step's layout; error-feedback residuals ride in
        # it so they are checkpointed (a restart must not reset them).
        self.use_pipeline = mesh is not None and shd.pipe_size(mesh) > 1
        state = init_train_state(cfg, self.opt,
                                 jax.random.PRNGKey(tcfg.seed), mesh)
        # a restore on a mesh lands each leaf in the same layout
        shardings = (None if mesh is None
                     else jax.tree.map(lambda a: a.sharding, state))

        self.start_step = 0
        if tcfg.ckpt_dir and checkpoint.latest_step(tcfg.ckpt_dir) is not None:
            try:
                state, self.start_step = checkpoint.restore(
                    tcfg.ckpt_dir, state, shardings=shardings)
            except (ValueError, TypeError) as e:
                if state.ef is None:
                    # template has no ef leaves but restore still failed —
                    # most likely a checkpoint from a compressed multi-pod
                    # run resumed under a different compress/mesh config
                    raise RuntimeError(
                        "checkpoint restore failed: if the checkpoint was "
                        "written by a compressed multi-pod run (TrainState"
                        ".ef present), restart with the same "
                        "compress_pod_grads / mesh configuration") from e
                # checkpoint predates the compressed-reduction config (no
                # ef leaves): restore everything else and restart the
                # error-feedback residuals from zero
                bare, self.start_step = checkpoint.restore(
                    tcfg.ckpt_dir, state._replace(ef=None),
                    shardings=shardings and shardings._replace(ef=None))
                state = bare._replace(ef=state.ef)
                print("[train] checkpoint carries no error-feedback "
                      "residuals; reinitialized ef to zero")
            state = jax.tree.map(jax.numpy.asarray, state)
        self.state = state

        if self.use_pipeline:
            if tcfg.num_microbatches > 1:
                # the pipeline step has no gradient-accumulation scan; its
                # microbatches are the gpipe stream (cfg.pipeline_
                # microbatches), not tcfg.num_microbatches — say so rather
                # than silently changing the effective-batch semantics
                print(f"[train] pipeline step ignores num_microbatches="
                      f"{tcfg.num_microbatches} (no gradient accumulation; "
                      f"gpipe streams cfg.pipeline_microbatches instead)")
            # clamp the pipeline microbatch count to divide the per-shard
            # batch (strictness stays in make_sharded_train_step for
            # direct callers; the Trainer knows the global batch and can
            # pick the nearest workable M)
            n_micro, local_b = pipeline_microbatch_clamp(
                cfg.pipeline_microbatches, tcfg.global_batch, mesh)
            if n_micro != cfg.pipeline_microbatches:
                print(f"[train] pipeline microbatches clamped "
                      f"{cfg.pipeline_microbatches} -> {n_micro} "
                      f"(per-shard batch {local_b})")
            step_fn = make_sharded_train_step(cfg, self.opt, mesh,
                                              num_microbatches=n_micro)
        else:
            step_fn = make_train_step(cfg, self.opt, mesh=mesh,
                                      num_microbatches=tcfg.num_microbatches)
        self.train_step = jax.jit(step_fn, donate_argnums=0)

    def run(self) -> List[Dict]:
        t = self.tcfg
        try:
            return self._run()
        finally:
            # join the in-flight async write even on a crash path: the
            # atomicity contract is that a checkpoint whose save() started
            # is either fully committed or absent — never torn.  Without
            # this, a failure a few (fast) steps after a save races the
            # writer thread and restart loses a committed-looking step.
            if t.ckpt_dir:
                self._ckpt.wait()

    def _run(self) -> List[Dict]:
        t = self.tcfg
        for step in range(self.start_step, t.steps):
            if t.fail_at_step is not None and step == t.fail_at_step:
                raise InjectedFailure(f"injected failure at step {step}")
            batch = self.data.batch_at(step)
            t0 = time.time()
            self.state, metrics = self.train_step(self.state, batch)
            metrics = {k: float(np.asarray(v)) for k, v in metrics.items()}
            dt = time.time() - t0
            self.step_times.append(dt)
            if len(self.step_times) >= 5:
                med = statistics.median(self.step_times[-50:])
                if dt > self.tcfg.straggler_factor * med \
                        and self.straggler_hook is not None:
                    self.straggler_hook(step, dt)
            metrics.update(step=step, seconds=dt)
            self.metrics_log.append(metrics)
            if t.ckpt_dir and (step + 1) % t.ckpt_every == 0:
                self._ckpt.save(t.ckpt_dir, step + 1, self.state)
        if t.ckpt_dir:
            self._ckpt.wait()
            checkpoint.save(t.ckpt_dir, t.steps, self.state)
        return self.metrics_log
