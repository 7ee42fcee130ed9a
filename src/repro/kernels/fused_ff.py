"""Fused LLaMA feed-forward front half: silu(x@Wg) * (x@Wu) — Pallas TPU
kernel (paper Table 2 "fused_ff").  Two f32 accumulators live in VMEM; the
SwiGLU epilogue fuses on the final K step."""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sched.spec import KernelSpec, TileIO


def _kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...].astype(jnp.float32)
    accg_ref[...] += jnp.dot(x, wg_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(x, wu_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        g = accg_ref[...]
        o_ref[...] = (g * jax.lax.logistic(g) * accu_ref[...]).astype(o_ref.dtype)


def fused_ff(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, *,
             bm: int = 128, bn: int = 128, bk: int = 128,
             interpret: bool = False) -> jax.Array:
    m, k = x.shape
    _, n = w_gate.shape
    assert w_gate.shape == w_up.shape == (k, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_ff",
    )(x, w_gate, w_up)


def make_spec(cfg: Dict) -> KernelSpec:
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]

    def tile_fn(x, wg, wu):
        return jnp.dot(x, wg), jnp.dot(x, wu)

    def epilogue_fn(g, u):
        return (jax.nn.silu(g) * u,)

    return KernelSpec(
        name="fused_ff",
        tile_fn=tile_fn,
        epilogue_fn=epilogue_fn,
        inputs=[TileIO("x", (bm, bk)), TileIO("wg", (bk, bn)),
                TileIO("wu", (bk, bn))],
        outputs=[TileIO("h", (bm, bn))],
        steps=3,
        accumulate=True,
        config=dict(cfg),
        flops_per_step=4 * bm * bn * bk,
    )


CONFIGS = [
    {"bm": 128, "bn": 128, "bk": 128},
    {"bm": 128, "bn": 128, "bk": 64},
    {"bm": 64, "bn": 256, "bk": 64},
    {"bm": 256, "bn": 128, "bk": 64},
]
