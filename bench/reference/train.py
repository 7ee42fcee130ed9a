"""Plain reference of one training step's loss and gradients for a dense
decoder, in float32 at the highest matmul precision.

It builds on :mod:`bench.reference.dense`: the same ``Dims``, the same
``layer_forward`` equations and the same weights, remade from the seed one
layer at a time through ``bench.weights`` (about 317 MB a layer in float32
at StableLM-3B's widths).  It imports nothing of the program under test.

The forward runs layer by layer and keeps each layer's input.  The loss is
the mean next-token cross-entropy over every position of every row, as a
training step defines it.  The backward walks the layers in reverse: it
remakes a layer's weights, takes ``jax.vjp`` of that layer at its kept
input, and keeps the squared norm of each weight's gradient.

Departures from a whole-model ``jax.grad``: none in the arithmetic; the
per-layer walk only bounds the memory.  The gradients themselves are not
kept, only their norms: the global norm over every leaf and the norm of
each leaf (a stacked per-layer leaf is one leaf, as in the program's
tree), keyed by the program's parameter paths.

Given the optimizer (``optim``), it also takes AdamW's first step from
zero moments: the gradients clipped to the global norm ``clip``, the
first moment ``(1 - b1) g`` and the second ``(1 - b2) g^2``, bias-corrected,
and the update ``-lr (m / (sqrt(v) + eps) + weight_decay p)``, with weight
decay on matrices only (every 2-D weight of a layer, the embedding and the
head; no norm and no bias).  The parameters are stored in the
configuration's dtype, so the new value is ``p + u`` rounded to it, with
``u`` rounded to it first.  It returns, per leaf, the norm of the first
moment and the norm of the parameters' change.  The clip needs the global
norm, so a second reverse walk recomputes each layer's gradients and
applies the step.

``precision="fp8"`` is the control: every matmul operand of the forward
rounded through float8 e4m3 as ``dense`` does it (a scale per output
channel of a weight, per row of an activation), and in the backward each
matmul's incoming gradient and its operands rounded the same way, each
with its own scales: what an fp8 training recipe does, one step below the
bfloat16 the program trains in.  The attention products stay float32 in
both, as in ``dense``.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import dense

_HI = jax.lax.Precision.HIGHEST


def dims(cfg: Dict) -> dense.Dims:
    """``dense.Dims`` of a configuration file that may name the q/k/v bias
    ``use_qkv_bias`` (StableLM) instead of ``attention_bias`` (Qwen2)."""
    c = dict(cfg)
    c.setdefault("attention_bias", bool(c.get("use_qkv_bias", False)))
    return dense.Dims(c)


@jax.custom_vjp
def _mm8(a, w):
    return jnp.matmul(dense._fp8(a, -1), dense._fp8(w, 0), precision=_HI)


def _mm8_fwd(a, w):
    return _mm8(a, w), (a, w)


def _mm8_bwd(res, g):
    a, w = res
    g8 = dense._fp8(g, -1)
    da = jnp.matmul(g8, dense._fp8(w, 1).T, precision=_HI)
    a2 = a.reshape(-1, a.shape[-1])
    dw = jnp.matmul(dense._fp8(a2, 0).T,
                    dense._fp8(g.reshape(-1, g.shape[-1]), 0), precision=_HI)
    return da, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)
_MM_F32 = dense._mm


def _mm_train(a, w, fp8: bool):
    return _mm8(a, w) if fp8 else _MM_F32(a, w, False)


@contextlib.contextmanager
def _training_matmuls():
    """``dense``'s matmul with a backward for fp8 (``dense._mm`` rounds
    only the forward; differentiated as it stands, the float8 casts would
    round every gradient through float8 without a scale and lose it)."""
    dense._mm = _mm_train
    try:
        yield
    finally:
        dense._mm = _MM_F32


def _head_loss(dm: dense.Dims, fp8: bool, x, scale, bias, head, labels):
    """Mean next-token cross-entropy of the final norm and head over x."""
    h = dense._norm(dm, x, scale, bias)
    logits = dense._mm(h, head, fp8)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def _sq(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sum(v * v) for k, v in tree.items()}


# matrices, which take weight decay (``dense.LAYER_PATHS`` keys and whole
# leaves); every other leaf is a norm or a bias
_DECAYED = frozenset({"wq", "wk", "wv", "wo", "gate", "up", "down",
                      "embed/table", "lm_head/out/w"})


def stored(x: jax.Array, dtype) -> jax.Array:
    """Float32 ``x`` rounded to ``dtype`` as memory holds it.  A
    ``.astype(dtype)`` inside a fusion may keep excess precision on a TPU
    (it rounds only where it is written out); ``reduce_precision`` always
    rounds."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _change_sq(optim: Dict, dtype, p, g, k, decay: bool):
    """Squared norm of one leaf's change in AdamW's first step from zero
    moments: ``p`` the initial values (float32, made through ``dtype``),
    ``g`` the gradient, ``k`` the clip's factor."""
    b1, b2 = float(optim["b1"]), float(optim["b2"])
    p = stored(p, dtype)
    g = k * g
    m = (1 - b1) * g / (1 - b1)
    v = (1 - b2) * g * g / (1 - b2)
    step = m / (jnp.sqrt(v) + float(optim["eps"]))
    if decay:
        step = step + float(optim["weight_decay"]) * p
    u = stored(-float(optim["lr"]) * step, dtype)
    return jnp.sum((stored(p + u, dtype) - p) ** 2)


def loss_and_grad_norms(cfg: Dict, seed: int, tokens: np.ndarray,
                        labels: np.ndarray, precision: str = "f32",
                        devices=None, optim: Optional[Dict] = None) -> Dict:
    """The reference's loss, global gradient norm and per-leaf gradient
    norms on the seed's weights and one batch (``tokens``, ``labels``:
    (B, S) int).  ``devices``: rows are split over them (B divisible by
    their number); every weight is whole on each.  Returns floats:
    ``loss``, ``grad_norm``, ``leaf_norms`` {path: norm}; with ``optim``
    (``lr`` of the first step, ``b1``, ``b2``, ``eps``, ``weight_decay``,
    ``clip``) also ``moment_norms`` and ``update_norms`` {path: norm} of
    AdamW's first step."""
    dm = dims(cfg)
    fp8 = precision == "fp8"
    shapes = dm.layer_shapes()
    make = W.layer_maker(seed, {dense.LAYER_PATHS[k]: v
                                for k, v in shapes.items()}, dm.dtype)
    split = whole = None
    if devices is not None and len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
        split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            "rows"))
        whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def rows_of(a):
        a = np.asarray(a, np.int32)
        return jax.device_put(a, split) if split else jnp.asarray(a)

    def weight(tree):
        return jax.device_put(tree, whole) if whole else tree

    def layer_weights(i):
        raw = weight(make(i))
        return {k: raw[dense.LAYER_PATHS[k]] for k in shapes}

    tok, lab = rows_of(tokens), rows_of(labels)

    with _training_matmuls():
        layer = jax.jit(functools.partial(dense.layer_forward, dm, fp8=fp8))

        @jax.jit
        def layer_grad(w, x, g):
            _, vjp = jax.vjp(lambda w_, x_: dense.layer_forward(
                dm, w_, x_, fp8), w, x)
            return vjp(g)

        head_grad = jax.jit(jax.value_and_grad(
            functools.partial(_head_loss, dm, fp8), argnums=(0, 1, 2, 3)))

        embed = weight(W.single_leaf(seed, "embed/table", (dm.vocab, dm.d),
                                     dm.dtype))
        x = embed[tok]
        kept = []
        for i in range(dm.layers):
            kept.append(x)
            x = layer(layer_weights(i), x)
        scale, bias, head = dense._head(dm, seed, fp8)
        if bias is None:
            bias = jnp.zeros_like(scale)
        scale, bias, head = weight((scale, bias, head))
        loss, (gx_top, gs, gb, gh) = head_grad(x, scale, bias, head, lab)
        del x
        sq = {"final_norm/scale": jnp.sum(gs * gs)}
        if dm.layernorm:
            sq["final_norm/bias"] = jnp.sum(gb * gb)
        gx = gx_top
        for i in reversed(range(dm.layers)):
            gw, gx = layer_grad(layer_weights(i), kept[i], gx)
            for k, v in jax.jit(_sq)(gw).items():
                p = f"{W.STACKED}/{dense.LAYER_PATHS[k]}"
                sq[p] = sq.get(p, 0.0) + v
        g_embed = weight(jnp.zeros((dm.vocab, dm.d), jnp.float32)
                         ).at[tok].add(gx)
        if dm.tied:
            g_embed = g_embed + gh.T
        else:
            sq["lm_head/out/w"] = jnp.sum(gh * gh)
        sq["embed/table"] = jnp.sum(g_embed * g_embed)
        sq = {k: float(v) for k, v in sq.items()}
        grad_norm = float(np.sqrt(sum(sq.values())))
        out = {"loss": float(loss), "grad_norm": grad_norm,
               "leaf_norms": {k: float(np.sqrt(v)) for k, v in sq.items()}}
        if optim is None:
            return out

        # AdamW's first step, now that the clip is known
        k = min(1.0, float(optim["clip"]) / (grad_norm + 1e-12))
        out["moment_norms"] = {p: (1 - float(optim["b1"])) * k * n
                               for p, n in out["leaf_norms"].items()}
        change = jax.jit(functools.partial(_change_sq, optim, dm.dtype),
                         static_argnums=3)
        dq = {"final_norm/scale": change(scale, gs, k, False),
              "embed/table": change(embed, g_embed, k, True)}
        if dm.layernorm:
            dq["final_norm/bias"] = change(bias, gb, k, False)
        if not dm.tied:
            dq["lm_head/out/w"] = change(
                weight(W.single_leaf(seed, "lm_head/out/w", (dm.d, dm.vocab),
                                     dm.dtype)), gh, k, True)
        del embed, g_embed, head
        gx = gx_top
        for i in reversed(range(dm.layers)):
            w = layer_weights(i)
            gw, gx = layer_grad(w, kept.pop(), gx)
            for n in shapes:
                p = f"{W.STACKED}/{dense.LAYER_PATHS[n]}"
                dq[p] = dq.get(p, 0.0) + change(w[n], gw[n], k,
                                                n in _DECAYED)
    out["update_norms"] = {p: float(np.sqrt(float(v)))
                           for p, v in dq.items()}
    return out
