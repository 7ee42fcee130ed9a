"""Mamba-2 SSD (state-space duality) chunk kernel — Pallas TPU.

Beyond the paper's kernel set: the hot kernel of the assigned mamba2/zamba2
architectures.  Chunked SSD: within-chunk work is a masked attention-like
matmul (MXU-friendly — the whole point of state-space *duality*), the
inter-chunk recurrence carries an (P, N) state in VMEM scratch across the
sequential chunk grid dimension.

Layout: heads fold into the batch grid axis.  x: (BH, S, P); a: (BH, S)
log-decay (<= 0); b, c: (BH, S, N).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sched.spec import KernelSpec, TileIO


def _kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # (chunk, P)
    a = a_ref[0, 0].astype(jnp.float32)         # (1, chunk) lane row
    b = b_ref[0].astype(jnp.float32)            # (chunk, N)
    c = c_ref[0].astype(jnp.float32)            # (chunk, N)

    # inclusive decay prefix seg[t] = sum_{r<=t} a[r] as a masked lane
    # reduction (Mosaic has no cumsum; an MXU pass would round to bf16)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.sum(jnp.where(cols <= rows, a, 0.0), axis=1,
                  keepdims=True)                # (chunk, 1)
    total = jnp.sum(a, axis=1, keepdims=True)   # (1, 1)

    # within-chunk: y_intra[t] = sum_{s<=t} e^{seg t - seg s} (c_t . b_s) x_s
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32)
    decay = jnp.exp(seg - seg.T)
    l_mat = jnp.where(rows >= cols, decay, 0.0)
    y_intra = jnp.dot(scores * l_mat, x, preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[t] = e^{seg t} c_t . state_in
    state = state_ref[...]                       # (P, N)
    y_inter = jnp.exp(seg) * jnp.dot(
        c, state.T, preferred_element_type=jnp.float32)

    o_ref[0] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: state' = e^{total} state + sum_s e^{total-seg s} x_s b_s^T
    w = jnp.exp(total - seg)
    state_ref[...] = (jnp.exp(total) * state
                      + jnp.dot((x * w).T, b,
                                preferred_element_type=jnp.float32))


def ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
        chunk: int = 64, interpret: bool = False) -> jax.Array:
    BH, S, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0 and a.shape == (BH, S)
    grid = (BH, S // chunk)
    # a as (BH, chunks, 1, chunk): each block's last two dims are whole,
    # so the TPU tiling accepts any chunk (a (1, chunk) block of the flat
    # (BH, S) array would not be)
    a4 = a.reshape(BH, S // chunk, 1, chunk)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, j: (h, j, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd",
    )(x, a4, b, c)


def make_spec(cfg: Dict) -> KernelSpec:
    chunk, p, n = cfg["chunk"], cfg["p"], cfg["n"]

    def tile_fn(x, a, b, c):
        # per-chunk SSD: intra-chunk masked matmul + state contribution
        seg = jnp.cumsum(a[:, 0])
        scores = jnp.dot(c, b.T)
        decay = jnp.exp(seg[:, None] - seg[None, :])
        y_intra = jnp.dot(scores * decay, x)
        state = jnp.dot((x * jnp.exp(seg[-1] - seg)[:, None]).T, b)
        y_inter = jnp.exp(seg)[:, None] * jnp.dot(c, state.T)
        return (y_intra + y_inter,)

    return KernelSpec(
        name="ssd",
        tile_fn=tile_fn,
        inputs=[TileIO("x", (chunk, p)), TileIO("a", (chunk, 1)),
                TileIO("b", (chunk, n)), TileIO("c", (chunk, n))],
        outputs=[TileIO("y", (chunk, p))],
        steps=3,
        accumulate=False,
        config=dict(cfg),
        flops_per_step=2 * chunk * chunk * (n + p) + 4 * chunk * n * p,
    )


CONFIGS = [
    {"chunk": 64, "p": 64, "n": 128},
    {"chunk": 128, "p": 64, "n": 128},
    {"chunk": 64, "p": 128, "n": 64},
]
