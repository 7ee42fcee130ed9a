"""Flash attention — Pallas TPU kernel (paper Table 2 "flash-attention").

Online-softmax with running (max, sum, acc) carried in VMEM scratch across
the sequential KV grid dimension; causal blocks above the diagonal are
skipped.  The q tile is loop-invariant in the TSASS lowering (loaded once
per q block), matching the real kernel's structure.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sched.spec import KernelSpec, TileIO

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(j * bk <= (i + 1) * bq - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bk: int = 128, causal: bool = True,
                    scale: float = None,
                    interpret: bool = False) -> jax.Array:
    """(B, H, S, D) attention.  B and H fold into one parallel grid axis."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    assert S % bq == 0 and Sk % bk == 0
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    grid = (B * H, S // bq, Sk // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)


def make_spec(cfg: Dict) -> KernelSpec:
    bq, bk, d = cfg["bq"], cfg["bk"], cfg["d"]

    def tile_fn(q, k, v):
        s = jnp.dot(q, k.T)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        ell = jnp.sum(p, axis=-1, keepdims=True)
        return (jnp.dot(p, v), ell)

    def epilogue_fn(acc, ell):
        return (acc / ell,)

    return KernelSpec(
        name="flash_attention",
        tile_fn=tile_fn,
        epilogue_fn=epilogue_fn,
        inputs=[TileIO("q", (bq, d), invariant=True),
                TileIO("k", (bk, d)), TileIO("v", (bk, d))],
        outputs=[TileIO("o", (bq, d))],
        steps=3,
        accumulate=True,
        config=dict(cfg),
        flops_per_step=4 * bq * bk * d,
    )


# paper configuration: B=1, n_head=4, seq=4096, d_head=32 (+ larger heads)
CONFIGS = [
    {"bq": 128, "bk": 128, "d": 64},
    {"bq": 128, "bk": 256, "d": 64},
    {"bq": 256, "bk": 128, "d": 64},
    {"bq": 128, "bk": 128, "d": 128},
]
