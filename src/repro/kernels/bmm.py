"""Batch matrix multiplication — Pallas TPU kernel (paper Table 2 "bmm";
the kernel whose §5.7.2 predicated-slot move the paper traces)."""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sched.spec import KernelSpec, TileIO


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0].astype(jnp.float32),
                            b_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def bmm(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
        bk: int = 128, interpret: bool = False) -> jax.Array:
    B, m, k = a.shape
    _, k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (B, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b_, i, j, kk: (b_, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, kk: (b_, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b_, i, j, kk: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="bmm",
    )(a, b)


def make_spec(cfg: Dict) -> KernelSpec:
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    return KernelSpec(
        name="bmm",
        tile_fn=lambda a, b: (jnp.dot(a, b),),
        inputs=[TileIO("a", (bm, bk)), TileIO("b", (bk, bn))],
        outputs=[TileIO("y", (bm, bn))],
        steps=3,
        accumulate=True,
        config=dict(cfg),
        flops_per_step=2 * bm * bn * bk,
    )


CONFIGS = [
    {"bm": 128, "bn": 128, "bk": 128},
    {"bm": 128, "bn": 128, "bk": 64},
    {"bm": 64, "bn": 64, "bk": 128},
    {"bm": 256, "bn": 64, "bk": 64},
]
