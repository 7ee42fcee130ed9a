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

Each :meth:`Trainer.step` runs inside a host span ``repro.train.step``
with ``data`` (the host makes the batch), ``put`` (the batch placed in the
step's layout), ``dispatch`` (the jitted call) and ``sync`` (the host
blocks on the step's metrics) inside it, and adds to ``Trainer.counters``:
``steps``, ``tokens``, ``host_s`` (the step less its ``sync``), ``data_s``
and ``sync_s``.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.ckpt import checkpoint
from repro.configs.base import ModelConfig
from repro.data.pipeline import make_data
from repro.dist import sharding as shd
from repro.optim import adamw as adamw_fn, linear_warmup_cosine
from repro.train.step import (decay_mask, init_train_state,
                              make_sharded_train_step, make_train_step)


class InjectedFailure(RuntimeError):
    pass


def span(name: str):
    """A host span ``repro.train.<name>`` in the profiler's trace; costs
    about a microsecond when no trace runs."""
    return jax.profiler.TraceAnnotation("repro.train." + name)


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
    """``params`` (optional) are the initial parameters, in the model's
    tree, placed in the step's layout; by default they are drawn from
    ``tcfg.seed``.  A checkpoint in ``tcfg.ckpt_dir`` wins over both."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                 straggler_hook: Optional[Callable[[int, float], None]] = None,
                 params: Optional[Dict] = None):
        self.cfg, self.tcfg, self.mesh = cfg, tcfg, mesh
        self.data = make_data(cfg, tcfg.seq_len, tcfg.global_batch, tcfg.seed)
        sched = linear_warmup_cosine(tcfg.lr, tcfg.warmup, tcfg.steps)
        self.opt = adamw_fn(sched, weight_decay=tcfg.weight_decay,
                            max_grad_norm=1.0, mask=decay_mask(cfg))
        self.straggler_hook = straggler_hook
        self.step_times: List[float] = []
        self.metrics_log: List[Dict] = []
        self._ckpt = checkpoint.AsyncCheckpointer()
        self.counters = {"steps": 0, "tokens": 0, "host_s": 0.0,
                         "data_s": 0.0, "sync_s": 0.0}

        # a mesh with a pipe axis >= 2 selects the shard_map pipeline step:
        # gpipe microbatches over `pipe`, compressed psum over `pod` (the
        # config opts in via pipeline_stages / compress_pod_grads — see
        # repro.launch.train, which sizes the mesh from them).  The state
        # is born in that step's layout; error-feedback residuals ride in
        # it so they are checkpointed (a restart must not reset them).
        self.use_pipeline = mesh is not None and shd.pipe_size(mesh) > 1
        key = None if params is not None else jax.random.PRNGKey(tcfg.seed)
        state = init_train_state(cfg, self.opt, key, mesh, params=params)
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
        self.next_step = self.start_step

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
        self._batch_sharding = None
        if mesh is not None and not self.use_pipeline:
            self._batch_sharding = lambda x: NamedSharding(
                mesh, shd.batch_spec(mesh, x.shape[0], ndim=x.ndim))

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

    def _put(self, batch: Dict[str, np.ndarray]) -> Dict:
        if self._batch_sharding is None:
            return jax.device_put(batch)
        return {k: jax.device_put(v, self._batch_sharding(v))
                for k, v in batch.items()}

    def step(self) -> Dict:
        """Run step ``next_step``: its batch, the jitted step, its metrics
        (as floats, with ``step`` and ``seconds``), a checkpoint when one is
        due.  The host blocks until the step's metrics are back."""
        t = self.tcfg
        step = self.next_step
        if t.fail_at_step is not None and step == t.fail_at_step:
            raise InjectedFailure(f"injected failure at step {step}")
        t0 = time.perf_counter()
        with span("step"):
            with span("data"):
                batch = self.data.batch_at(step)
            t1 = time.perf_counter()
            with span("put"):
                placed = self._put(batch)
            with span("dispatch"):
                self.state, metrics = self.train_step(self.state, placed)
            t2 = time.perf_counter()
            with span("sync"):
                metrics = {k: float(np.asarray(v)) for k, v in metrics.items()}
        t3 = time.perf_counter()
        dt = t3 - t0
        c = self.counters
        c["steps"] += 1
        c["tokens"] += int(batch["tokens"].size)
        c["data_s"] += t1 - t0
        c["sync_s"] += t3 - t2
        c["host_s"] += dt - (t3 - t2)
        self.step_times.append(dt)
        if len(self.step_times) >= 5:
            med = statistics.median(self.step_times[-50:])
            if dt > t.straggler_factor * med \
                    and self.straggler_hook is not None:
                self.straggler_hook(step, dt)
        metrics.update(step=step, seconds=dt)
        self.metrics_log.append(metrics)
        self.next_step = step + 1
        if t.ckpt_dir and self.next_step % t.ckpt_every == 0:
            self._ckpt.save(t.ckpt_dir, self.next_step, self.state)
        return metrics

    def _run(self) -> List[Dict]:
        t = self.tcfg
        while self.next_step < t.steps:
            self.step()
        if t.ckpt_dir:
            self._ckpt.wait()
            checkpoint.save(t.ckpt_dir, t.steps, self.state)
        return self.metrics_log
