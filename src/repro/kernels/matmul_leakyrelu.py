"""Fused GEMM + LeakyReLU epilogue — Pallas TPU kernel (paper Table 2,
Fig. 6 "mmLeakyReLu"; the kernel whose §5.7.1 reuse-cache move the paper
traces).

MXU-aligned BlockSpec tiling with an f32 VMEM accumulator; the K grid
dimension is 'arbitrary' (sequential) so the accumulator persists across
K steps and the epilogue fires on the last one.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sched.scenario import Scenario, scenario_steps
from repro.sched.spec import KernelSpec, TileIO


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, negative_slope: float, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...]
        o_ref[...] = jnp.where(y >= 0, y, negative_slope * y).astype(o_ref.dtype)


def matmul_leakyrelu(a: jax.Array, b: jax.Array, *, bm: int = 128,
                     bn: int = 128, bk: int = 128,
                     negative_slope: float = 0.01,
                     interpret: bool = False) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (a.shape, b.shape, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, negative_slope=negative_slope, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="matmul_leakyrelu",
    )(a, b)


# ---------------------------------------------------------------------------
# schedule-optimizer integration
# ---------------------------------------------------------------------------

def make_spec(cfg: Dict, *, scenario: Optional[Scenario] = None
              ) -> KernelSpec:
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    dtype = scenario.dtype if scenario is not None else "bf16"
    return KernelSpec(
        name="matmul_leakyrelu",
        tile_fn=lambda a, b: (jnp.dot(a, b),),
        epilogue_fn=lambda acc: (jnp.where(acc >= 0, acc, 0.01 * acc),),
        inputs=[TileIO("a", (bm, bk), dtype=dtype),
                TileIO("b", (bk, bn), dtype=dtype)],
        outputs=[TileIO("y", (bm, bn), dtype=dtype)],
        steps=scenario_steps(scenario, bm, default=3),
        accumulate=True,
        config=dict(cfg),
        flops_per_step=2 * bm * bn * bk,
    )


CONFIGS = [
    {"bm": 128, "bn": 128, "bk": 128},
    {"bm": 128, "bn": 128, "bk": 64},
    {"bm": 64, "bn": 128, "bk": 128},
    {"bm": 128, "bn": 256, "bk": 64},
    {"bm": 256, "bn": 128, "bk": 64},
]
