"""Random weights from the seed, made by the benchmark.

Every leaf's values depend only on the seed, the leaf's path in the
parameter tree and, for a stacked per-layer leaf, the layer index.  So the
program's whole tree is one jitted call on the device, and the reference
remakes any single layer alone with the same values.

The stream is a counter hashed twice with a 32-bit finalizer (MurmurHash3's
``fmix32``), keyed per (seed, path, layer) on the host: a few integer
operations an element, where JAX's threefry takes tens, and bit-for-bit the
same on every backend.  Values are uniform with the wanted standard
deviation.

Initial scales (the benchmark's own, so that a random model has sane
logits): matrices with standard deviation ``1/sqrt(fan_in)``; the
embedding and the output head 0.02 (logits of standard deviation about 1
at ``hidden_size`` 2560); norm scales ``1 + 0.1 u`` and every bias
``0.02 u`` (``u`` of unit variance), so that a path which drops a scale or
a bias shows.
"""

from __future__ import annotations

import hashlib
import math
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_STD = 0.1
STACKED = "layers"      # the tree's per-layer leaves, stacked on axis 0
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def stream_key(seed: int, path: str, layer: int = -1) -> Tuple[int, int]:
    """Two 32-bit keys for one leaf (and layer) of one seed."""
    h = hashlib.blake2b(f"{int(seed)}/{path}/{layer}".encode(),
                        digest_size=8).digest()
    return int.from_bytes(h[:4], "little"), int.from_bytes(h[4:], "little")


def _fmix(x: jax.Array) -> jax.Array:
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def _unit(shape: Sequence[int], k1, k2) -> jax.Array:
    """Uniform values of mean 0 and variance 1 over ``shape``; ``k1``,
    ``k2`` are uint32 scalars, or (L, 1, ...) arrays for L layers."""
    n = math.prod(shape)
    c = jnp.arange(n, dtype=jnp.uint32).reshape(tuple(shape))
    x = _fmix(_fmix(c + k1) ^ k2)
    u = ((x >> 8).astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))
    return (2.0 * u - 1.0) * math.sqrt(3.0)


def _scaled(path: str, shape: Sequence[int], unit: jax.Array) -> jax.Array:
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0 + NORM_STD * unit
    if name == "bias" or name.endswith("_b"):
        return BIAS_STD * unit
    if path.startswith("embed/") or path.startswith("lm_head/"):
        return EMBED_STD * unit
    return unit / math.sqrt(shape[0])


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def make_params(seed: int, shapes):
    """The program's parameter tree (``shapes`` as ``jax.eval_shape`` gives
    it) in each leaf's dtype, made on the device in one jitted call."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = []
    for path, sd in flat:
        p = path_str(path)
        if p.startswith(STACKED + "/"):
            ks = [stream_key(seed, p, i) for i in range(sd.shape[0])]
            keys.append(tuple(jnp.asarray([k[j] for k in ks], jnp.uint32)
                              .reshape((-1,) + (1,) * (len(sd.shape) - 1))
                              for j in (0, 1)))
        else:
            keys.append(tuple(jnp.uint32(k) for k in stream_key(seed, p)))

    def make(keys):
        leaves = []
        for (path, sd), (k1, k2) in zip(flat, keys):
            p = path_str(path)
            shape = sd.shape[1:] if p.startswith(STACKED + "/") else sd.shape
            leaves.append(_scaled(p, shape, _unit(shape, k1, k2))
                          .astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tdef, leaves)

    return jax.jit(make)(keys)


def layer_maker(seed: int, paths: Dict[str, Sequence[int]], dtype
                ) -> Callable:
    """``make(i)`` -> {path: float32 array} for layer ``i`` of the stacked
    leaves in ``paths`` (path -> per-layer shape), rounded through
    ``dtype`` exactly as the program's copy was.  Compiled once."""

    @jax.jit
    def draw(keys):
        return {p: _scaled(f"{STACKED}/{p}", shape,
                           _unit(shape, *keys[p])).astype(dtype)
                .astype(jnp.float32) for p, shape in paths.items()}

    def make(i: int):
        return draw({p: tuple(jnp.uint32(k) for k in stream_key(
            seed, f"{STACKED}/{p}", i)) for p in paths})

    return make


def single_leaf(seed: int, path: str, shape: Sequence[int], dtype) -> jax.Array:
    """An unstacked leaf (embedding, head, final norm) in float32, rounded
    through ``dtype``."""
    k1, k2 = (jnp.uint32(k) for k in stream_key(seed, path))
    return jax.jit(lambda a, b: _scaled(path, shape, _unit(shape, a, b))
                   .astype(dtype).astype(jnp.float32))(k1, k2)
