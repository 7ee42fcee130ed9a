"""Plain reference of a dense decoder (Qwen2 and StableLM equations), in
float32 at the highest matmul precision.

It reads the published configuration keys of a ``bench/configs`` file
(``hidden_size``, ``num_attention_heads``, ``rms_norm_eps`` or
``layer_norm_eps``, ``partial_rotary_factor``, ``tie_word_embeddings`` and
so on) and remakes the weights from the seed through ``bench.weights``, one
layer at a time, so that it fits beside nothing else on one chip.  It
imports nothing of the program under test.

Equations (pre-norm decoder):

    h = norm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k) on the first ``partial_rotary_factor`` of each
           head's dims, rotate-half convention, inv_freq = theta^(-2i/r)
    x = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
    x = x + (silu(norm(x) Wgate) * (norm(x) Wup)) Wdown
    logits = norm(x) E^T  (tied)  or  norm(x) Whead

``norm`` is RMSNorm with a scale (``rms_norm_eps``) or LayerNorm with a
scale and a bias (``layer_norm_eps``).

``precision="fp8"`` is the control: every matmul operand rounded through
float8 e4m3 with a scale per output channel (weights) or per row
(activations), the step below bfloat16 that a serving change might take.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

# Where the benchmark's weight generator keeps each tensor of a dense
# layer: the program's parameter paths, which key the seeded streams.
LAYER_PATHS = {
    "ln1": "ln1/scale", "ln1_b": "ln1/bias",
    "wq": "attn/wq/w", "bq": "attn/wq/w_b",
    "wk": "attn/wk/w", "bk": "attn/wk/w_b",
    "wv": "attn/wv/w", "bv": "attn/wv/w_b",
    "wo": "attn/wo/w",
    "ln2": "ln2/scale", "ln2_b": "ln2/bias",
    "gate": "mlp/gate/w", "up": "mlp/up/w", "down": "mlp/down/w",
}
FP8_MAX = 448.0


class Dims:
    """The equations' sizes and options from a configuration file."""

    def __init__(self, c: Dict):
        self.d = int(c["hidden_size"])
        self.ff = int(c["intermediate_size"])
        self.layers = int(c["num_hidden_layers"])
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c.get("num_key_value_heads", self.heads))
        self.hd = int(c.get("head_dim") or self.d // self.heads)
        self.vocab = int(c["vocab_size"])
        self.theta = float(c["rope_theta"])
        self.rot = int(self.hd * float(c.get("partial_rotary_factor", 1.0)))
        self.layernorm = "layer_norm_eps" in c
        self.eps = float(c["layer_norm_eps"] if self.layernorm
                         else c["rms_norm_eps"])
        self.bias = bool(c["attention_bias"])
        self.tied = bool(c["tie_word_embeddings"])
        self.dtype = jnp.dtype(c["torch_dtype"])

    def layer_shapes(self) -> Dict[str, tuple]:
        d, ff = self.d, self.ff
        q, kv = self.heads * self.hd, self.kv_heads * self.hd
        shapes = {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                  "wo": (q, d), "ln2": (d,), "gate": (d, ff), "up": (d, ff),
                  "down": (ff, d)}
        if self.bias:
            shapes.update(bq=(q,), bk=(kv,), bv=(kv,))
        if self.layernorm:
            shapes.update(ln1_b=(d,), ln2_b=(d,))
        return shapes


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round through float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _norm(dm: Dims, x, scale, bias=None):
    if dm.layernorm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + dm.eps) * scale + bias
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + dm.eps) * scale


def _rope(dm: Dims, x, positions):
    """x (B, S, H, hd); rotate-half on the first ``dm.rot`` dims."""
    r = dm.rot
    inv = dm.theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions[:, :, None].astype(jnp.float32) * inv          # B S r/2
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def layer_forward(dm: Dims, w: Dict, x: jax.Array, fp8: bool = False):
    """One decoder layer over (B, S, d) float32, causal from position 0."""
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _norm(dm, x, w["ln1"], w.get("ln1_b"))
    q = _mm(h, w["wq"], fp8) + (w["bq"] if dm.bias else 0.0)
    k = _mm(h, w["wk"], fp8) + (w["bk"] if dm.bias else 0.0)
    v = _mm(h, w["wv"], fp8) + (w["bv"] if dm.bias else 0.0)
    q = _rope(dm, q.reshape(B, S, dm.heads, dm.hd), pos)
    k = _rope(dm, k.reshape(B, S, dm.kv_heads, dm.hd), pos)
    v = v.reshape(B, S, dm.kv_heads, dm.hd)
    rep = dm.heads // dm.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(dm.hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(B, S, dm.heads * dm.hd), w["wo"], fp8)
    h = _norm(dm, x, w["ln2"], w.get("ln2_b"))
    g = _mm(h, w["gate"], fp8)
    u = _mm(h, w["up"], fp8)
    return x + _mm(jax.nn.silu(g) * u, w["down"], fp8)


def _head(dm: Dims, seed: int, fp8: bool):
    """(final norm, head matrix (d, V)) in float32."""
    scale = W.single_leaf(seed, "final_norm/scale", (dm.d,), dm.dtype)
    bias = (W.single_leaf(seed, "final_norm/bias", (dm.d,), dm.dtype)
            if dm.layernorm else None)
    if dm.tied:
        head = W.single_leaf(seed, "embed/table", (dm.vocab, dm.d),
                             dm.dtype).T
    else:
        head = W.single_leaf(seed, "lm_head/out/w", (dm.d, dm.vocab),
                             dm.dtype)
    return scale, bias, head


def served_logits(cfg: Dict, seed: int, seqs: Sequence[Sequence[int]],
                  rows: Sequence[Sequence[int]],
                  precisions: Sequence[str] = ("f32",), *,
                  n_seqs: int = 0, length: int = 0,
                  head_rows: int = 256) -> List[np.ndarray]:
    """Logits of the reference at chosen positions of each sequence.

    ``seqs`` are token sequences (prompt + served tokens), ``rows[j]`` the
    positions of ``seqs[j]`` whose logits are wanted.  Returns one
    (sum(len(rows)), vocab) float32 array per entry of ``precisions``
    ("f32" or "fp8"), in the order of ``seqs`` then ``rows``.  Sequences
    are padded to ``n_seqs`` x ``length`` and the output head runs on
    ``head_rows`` rows a call, so that the shapes are the same from run
    to run; padding comes after every real position, so the causal mask
    keeps it out of them."""
    dm = Dims(cfg)
    L = max(length, max(len(s) for s in seqs))
    tok = np.zeros((max(n_seqs, len(seqs)), L), np.int32)
    for j, s in enumerate(seqs):
        tok[j, :len(s)] = s
    shapes = dm.layer_shapes()
    make = W.layer_maker(seed, {LAYER_PATHS[k]: v for k, v in shapes.items()},
                         dm.dtype)
    embed = W.single_leaf(seed, "embed/table", (dm.vocab, dm.d), dm.dtype)
    x0 = embed[jnp.asarray(tok)]
    del embed
    sel_b = np.concatenate([np.full(len(r), j, np.int32)
                            for j, r in enumerate(rows)])
    sel_s = np.concatenate([np.asarray(r, np.int32) for r in rows])
    n = len(sel_b)
    pad = -n % head_rows
    sel_b = np.concatenate([sel_b, np.zeros(pad, np.int32)])
    sel_s = np.concatenate([sel_s, np.zeros(pad, np.int32)])

    step = jax.jit(layer_forward, static_argnums=(0, 3))
    head_fn = jax.jit(lambda h, s, b, w, fp8: _mm(_norm(dm, h, s, b), w, fp8),
                      static_argnums=4)
    out = []
    for prec in precisions:
        fp8 = prec == "fp8"
        x = x0
        for i in range(dm.layers):
            raw = make(i)
            w = {k: raw[LAYER_PATHS[k]] for k in shapes}
            x = step(dm, w, x, fp8)
        scale, bias, head = _head(dm, seed, fp8)
        h = x[sel_b, sel_s]
        parts = [np.asarray(head_fn(h[i:i + head_rows], scale, bias, head,
                                    fp8))
                 for i in range(0, len(sel_b), head_rows)]
        out.append(np.concatenate(parts)[:n])
    return out


def logit_gaps(ref_rows: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row: how far the reference's logit for ``tokens`` lies below the
    reference's best, in standard deviations of that row's logits."""
    chosen = ref_rows[np.arange(len(tokens)), tokens]
    return (ref_rows.max(axis=1) - chosen) / ref_rows.std(axis=1)
