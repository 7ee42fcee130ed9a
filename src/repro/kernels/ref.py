"""Pure-jnp oracles for every Pallas kernel (the allclose reference).

These are also the XLA fallback path used by the model stack on CPU and in
the dry-run (Pallas lowers for the TPU target; on this host the kernels are
validated in interpret mode against these functions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_leakyrelu(a: jax.Array, b: jax.Array,
                     negative_slope: float = 0.01) -> jax.Array:
    """Fused GEMM + LeakyReLU epilogue (paper Table 2: mmLeakyReLu)."""
    y = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return jnp.where(y >= 0, y, negative_slope * y).astype(a.dtype)


def bmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batch matrix multiplication (paper Table 2: bmm)."""
    return jnp.einsum("bmk,bkn->bmn", a.astype(jnp.float32),
                      b.astype(jnp.float32)).astype(a.dtype)


def fused_ff(x: jax.Array, w_gate: jax.Array, w_up: jax.Array) -> jax.Array:
    """Fused LLaMA-style feed-forward front half: silu(x@Wg) * (x@Wu)
    (paper Table 2: fused_ff)."""
    xf = x.astype(jnp.float32)
    g = jnp.dot(xf, w_gate.astype(jnp.float32))
    u = jnp.dot(xf, w_up.astype(jnp.float32))
    return (jax.nn.silu(g) * u).astype(x.dtype)


def softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    """Numerically-stable row softmax (paper Table 2: softmax)."""
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=axis, keepdims=True)
    e = jnp.exp(xf - m)
    return (e / jnp.sum(e, axis=axis, keepdims=True)).astype(x.dtype)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Root-mean-square layer normalization (paper Table 2: rmsnorm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
            ).astype(x.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: float = None) -> jax.Array:
    """Exact attention oracle, (B, H, S, D) layout (paper Table 2:
    flash-attention)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ssd_chunk(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
              chunk: int = 64) -> jax.Array:
    """Mamba-2 SSD (state-space duality) oracle: sequential scan semantics.

    x: (B, S, H, P) inputs; a: (B, S, H) log-decay (<=0); b,c: (B, S, G, N)
    input/output projections (G groups broadcast over H heads).
    Returns y: (B, S, H, P).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    bf = jnp.repeat(b, rep, axis=2).astype(jnp.float32)   # (B,S,H,N)
    cf = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)

    def step(state, inp):
        xt, at, bt, ct = inp       # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(at)[..., None, None]               # (B,H,1,1)
        state = state * decay + xt[..., None] * bt[..., None, :]  # (B,H,P,N)
        y = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, y

    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(af, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """:func:`ssd_chunk` in the Pallas ``ssd`` kernel's layout, one head
    per row: x (BH, S, P), a (BH, S), b/c (BH, S, N) -> y (BH, S, P)."""
    y = ssd_chunk(x[:, :, None, :], a[:, :, None], b[:, :, None, :],
                  c[:, :, None, :])
    return y[:, :, 0, :]
