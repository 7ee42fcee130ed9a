"""Serving: KV caches + the single-token decode step for every family.

Decode is deliberately *unrolled* over layers (unlike the scanned training
path): each layer owns its cache pytree, so per-layer cache shapes can
differ — gemma's local layers keep a bounded ``window``-sized ring buffer
while its global layers keep the full sequence; mamba layers keep an O(1)
recurrent state.

The step takes its weights in the serving form :func:`serving_params`
makes once, at load: every stacked layer group as a list of per-layer
pytrees.  A step that sliced layer ``i`` out of a stacked array instead
made the compiler re-lay out every layer's attention matrices on every
pass (a slice fusion per matrix and a copy per layer, growing with depth).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import nn
from repro.configs.base import ModelConfig
from repro.models.lm import (_apply_mlp, _apply_norm, mla_config, moe_config,
                             ssm_config, window_schedule)
from repro.nn.attention import (NO_WINDOW, masked_decode_attention,
                                paged_decode_attention, paged_update_cache)
from repro.nn.mla import (apply_mla_decode, apply_mla_paged_decode,
                          init_mla_cache, init_paged_mla_cache)
from repro.nn.ssm import apply_ssm_decode, init_ssm_cache

_NEG = -1e30

# The stacked layer groups the decode step indexes by layer; embedding, head,
# final norm, ``shared_block`` and the encoder's ``enc_layers`` stay as the
# model makes them.
_LAYER_GROUPS = ("layers", "dense_layers", "dec_layers")


# ---------------------------------------------------------------------------
# the serving form of the weights
# ---------------------------------------------------------------------------

def _less_layer(sharding):
    """A stacked leaf's sharding without its leading layer axis."""
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, PartitionSpec(*sharding.spec[1:]),
                             memory_kind=sharding.memory_kind)
    return sharding


@functools.lru_cache(maxsize=None)
def _unstacker(sharding):
    return jax.jit(lambda a: [a[i] for i in range(a.shape[0])],
                   out_shardings=sharding)


def _unstack(a, consume: bool) -> List:
    """One stacked leaf -> its per-layer leaves, abstract or on device."""
    if isinstance(a, jax.ShapeDtypeStruct):
        s = jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                 sharding=_less_layer(a.sharding))
        return [s] * a.shape[0]
    if isinstance(a, jax.core.Tracer):
        return [a[i] for i in range(a.shape[0])]
    parts = _unstacker(_less_layer(a.sharding))(a)
    if consume:
        jax.block_until_ready(parts)
        a.delete()
    return parts


def serving_params(params: Dict, *, consume: bool = False) -> Dict:
    """The weights as the decode step takes them: each stacked layer group
    in ``params`` (``layers``, ``dense_layers``, ``dec_layers``) becomes a
    list of per-layer pytrees (leaf ``a`` -> ``a[i]`` in entry ``i``); the
    other entries pass through.

    Arrays on a device are split one stacked leaf at a time, each part
    keeping the leaf's sharding less the layer axis; with ``consume`` each
    stacked leaf is deleted as soon as its parts exist, so the layout never
    holds more than one leaf twice (the caller's arrays are gone after).
    Abstract leaves (``ShapeDtypeStruct``) give abstract parts, and traced
    ones are indexed inside the trace."""
    out = dict(params)
    for key in _LAYER_GROUPS:
        if key not in params:
            continue
        leaves, treedef = jax.tree.flatten(params[key])
        cols = [_unstack(a, consume) for a in leaves]
        out[key] = [treedef.unflatten([c[i] for c in cols])
                    for i in range(len(cols[0]))]
    return out


def _cache_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _attn_cache(cfg: ModelConfig, batch: int, length: int) -> Dict:
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, _cache_dtype(cfg)),
            "v": jnp.zeros(shape, _cache_dtype(cfg))}


def init_caches(cfg: ModelConfig, batch: int, max_seq: int) -> List:
    """One cache pytree per layer (family-dependent shapes)."""
    caches: List = []
    if cfg.family in ("dense", "moe"):
        wins = [int(w) for w in window_schedule(cfg)]
        for li in range(cfg.n_layers):
            if cfg.mla:
                caches.append(init_mla_cache(mla_config(cfg), batch, max_seq,
                                             _cache_dtype(cfg)))
            else:
                length = max_seq if wins[li] >= NO_WINDOW \
                    else min(wins[li], max_seq)
                caches.append(_attn_cache(cfg, batch, length))
    elif cfg.family == "ssm":
        for _ in range(cfg.n_layers):
            caches.append(init_ssm_cache(ssm_config(cfg), batch,
                                         _cache_dtype(cfg)))
    elif cfg.family == "hybrid":
        for _ in range(cfg.n_layers):
            caches.append(init_ssm_cache(ssm_config(cfg), batch,
                                         _cache_dtype(cfg)))
        for _ in range(cfg.n_layers // cfg.shared_attn_every):
            caches.append(_attn_cache(cfg, batch, max_seq))
    elif cfg.family == "encdec":
        from repro.configs.whisper_large_v3 import ENC_LEN_DECODE
        for _ in range(cfg.dec_layers):
            c = _attn_cache(cfg, batch, max_seq)
            c["ck"] = jnp.zeros((batch, ENC_LEN_DECODE, cfg.n_kv_heads,
                                 cfg.head_dim), _cache_dtype(cfg))
            c["cv"] = jnp.zeros_like(c["ck"])
            caches.append(c)
    else:
        raise ValueError(cfg.family)
    return caches


def paged_cache_kinds(cfg: ModelConfig) -> List[str]:
    """Per-cache-entry layout under paging, parallel to the cache list:
    ``"paged"`` — block-major physical pages addressed through the block
    table (attention K/V, MLA latent); ``"slot"`` — per-slot rows gathered/
    scattered by slot index (recurrent SSM/conv state has no sequence
    dimension to page)."""
    if cfg.family in ("dense", "moe"):
        return ["paged"] * cfg.n_layers
    if cfg.family == "ssm":
        return ["slot"] * cfg.n_layers
    if cfg.family == "hybrid":
        return (["slot"] * cfg.n_layers
                + ["paged"] * (cfg.n_layers // cfg.shared_attn_every))
    raise ValueError(f"paged serving does not support family {cfg.family!r}")


def _paged_attn_cache(cfg: ModelConfig, num_blocks: int,
                      block_size: int) -> Dict:
    # Each page row holds every KV head side by side, (KH * D): the page
    # write, the gather and the step's boundary then share one layout, so
    # the compiled step updates the pages in place instead of re-laying
    # them out (the heads are split off only after the gather).
    shape = (num_blocks, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, _cache_dtype(cfg)),
            "v": jnp.zeros(shape, _cache_dtype(cfg))}


def init_paged_caches(cfg: ModelConfig, num_blocks: int, block_size: int,
                      num_slots: int) -> List:
    """Block-major cache pytree for the paged serve path.

    Attention/MLA entries hold ``num_blocks`` physical pages of
    ``block_size`` positions shared by every request — memory scales with
    the KV budget, not ``num_slots × max_seq``.  Windowed layers also
    store absolute positions (the slot cache's ring is reconstructed by a
    trailing-window gather): they spend ``max_seq/window`` more bytes per
    layer than the ring, the price of sharing and remapping pages.
    Recurrent entries keep ``num_slots + 1`` per-slot rows (scratch row
    included)."""
    caches: List = []
    for kind in paged_cache_kinds(cfg):
        if kind == "slot":
            caches.append(init_ssm_cache(ssm_config(cfg), num_slots + 1,
                                         _cache_dtype(cfg)))
        elif cfg.family in ("dense", "moe") and cfg.mla:
            caches.append(init_paged_mla_cache(mla_config(cfg), num_blocks,
                                               block_size, _cache_dtype(cfg)))
        else:
            caches.append(_paged_attn_cache(cfg, num_blocks, block_size))
    return caches


# ---------------------------------------------------------------------------
# per-layer decode attention
# ---------------------------------------------------------------------------

def _positions(pos, batch: int) -> jax.Array:
    """(B, 1) rope positions from a scalar or per-row ``(B,)`` pos."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos[:, None] if pos.ndim >= 1 else jnp.full((batch, 1), pos)


def _project_qkv(cfg: ModelConfig, p: Dict, x: jax.Array,
                 positions: jax.Array):
    """The decode-step q/k/v projection (+qk-norm, +rope) — shared by the
    slot-cache path and the paged block-table path so both produce
    bit-identical per-token K/V before the cache write."""
    from repro.nn.core import apply_dense
    B = x.shape[0]
    q = apply_dense(p["wq"], x).reshape(B, 1, cfg.n_heads, cfg.head_dim)
    k = apply_dense(p["wk"], x).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    v = apply_dense(p["wv"], x).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.apply_rmsnorm(p["q_norm"], q)
        k = nn.apply_rmsnorm(p["k_norm"], k)
    q = nn.apply_rope(q, positions, cfg.rope_theta, cfg.rope_dims)
    k = nn.apply_rope(k, positions, cfg.rope_theta, cfg.rope_dims)
    return q, k, v


def _attn_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                 pos, window: int) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, d); ring buffer for local windows, absolute cache else.

    ``pos`` is a scalar (all rows at the same position — static batch)
    or a ``(B,)`` vector (ragged rows — continuous batching), in which
    case the key mask becomes per-row ``(B, S)``."""
    B = x.shape[0]
    positions = _positions(pos, B)
    q, k, v = _project_qkv(cfg, p, x, positions)

    ragged = jnp.asarray(pos).ndim >= 1
    S = cache["k"].shape[1]
    ring = window < NO_WINDOW and S <= window
    if ring:
        with jax.named_scope("kv_write"):
            k_cache = jnp.concatenate(
                [cache["k"][:, 1:], k.astype(cache["k"].dtype)], axis=1)
            v_cache = jnp.concatenate(
                [cache["v"][:, 1:], v.astype(cache["v"].dtype)], axis=1)
        if ragged:
            k_positions = positions - (S - 1) + jnp.arange(S)[None]  # (B,S)
        else:
            k_positions = pos - (S - 1) + jnp.arange(S)
        mask = k_positions >= 0
    else:
        with jax.named_scope("kv_write"):
            k_cache = nn.update_cache(cache["k"], k, pos)
            v_cache = nn.update_cache(cache["v"], v, pos)
        if ragged:
            k_positions = jnp.arange(S)[None]                        # (B,S)
            mask = (k_positions <= positions) & \
                   (k_positions > positions - window)
        else:
            k_positions = jnp.arange(S)
            mask = (k_positions <= pos) & (k_positions > pos - window)

    with jax.named_scope("attend"):
        o = masked_decode_attention(q, k_cache, v_cache, mask)
    out = nn.out_project(p, o)
    return out, {"k": k_cache, "v": v_cache, **{kk: vv for kk, vv in
                                                cache.items()
                                                if kk not in ("k", "v")}}


def _attn_decode_paged(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                       block_table: jax.Array, pos: jax.Array,
                       window: int, max_seq: int,
                       write_mask: jax.Array) -> Tuple[jax.Array, Dict]:
    """The paged analogue of :func:`_attn_decode`: scatter this token's K/V
    into the physical pages (rows of ``KH * D``) through the block table,
    then attend over a gather whose width matches the slot cache's
    length — so the outputs are bit-identical to the slot path's."""
    B = x.shape[0]
    positions = _positions(pos, B)
    q, k, v = _project_qkv(cfg, p, x, positions)
    with jax.named_scope("kv_write"):
        k_pages = paged_update_cache(cache["k"], k.reshape(B, 1, -1),
                                     block_table, pos, write_mask=write_mask)
        v_pages = paged_update_cache(cache["v"], v.reshape(B, 1, -1),
                                     block_table, pos, write_mask=write_mask)
    width = max_seq if window >= NO_WINDOW else min(window, max_seq)
    with jax.named_scope("attend"):
        o = paged_decode_attention(q, k_pages, v_pages, block_table, pos,
                                   window=window, width=width)
    return nn.out_project(p, o), {"k": k_pages, "v": v_pages}


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------
#
# ``decode_step`` takes ``params`` in the serving form (:func:`serving_params`).
# Named scopes (``layer<i>/attn/kv_write``, ``layer<i>/attn/attend``,
# ``layer<i>/mlp``, ``head``) land in the compiled HLO's ``op_name``
# metadata, so a device trace's ops map back to source.

def _dense_layer_params(params: Dict, li: int, dense_head: int) -> Dict:
    """Layer ``li``'s weights: a moe model's first ``dense_head`` layers
    are its ``dense_layers``, the rest its ``layers``."""
    if li < dense_head:
        return params["dense_layers"][li]
    return params["layers"][li - dense_head]


def _mlp_block(cfg: ModelConfig, p: Dict, x: jax.Array, routed: bool,
               mesh) -> jax.Array:
    """The residual MLP (routed experts in a moe layer past the dense head)."""
    with jax.named_scope("mlp"):
        h = _apply_norm(cfg, p["ln2"], x)
        if cfg.family == "moe" and routed:
            return x + nn.apply_moe(p["moe"], h, moe_config(cfg), mesh=mesh)
        return x + _apply_mlp(cfg, p["mlp"], h)


def _head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    """Final norm and output head: (B, 1, d) -> logits (B, vocab)."""
    with jax.named_scope("head"):
        x = _apply_norm(cfg, params["final_norm"], x)
        logits = (nn.unembed(params["embed"], x) if cfg.tie_embeddings
                  else nn.apply_lm_head(params["lm_head"], x))
        return logits[:, 0]




class Pages(NamedTuple):
    """How a pass reaches the engine's paged caches (see
    :func:`paged_cache_kinds`): each lane's ``block_table`` row (B,
    blocks_per_slot) of physical page ids, the (B,) ``write_mask`` that
    suppresses the page write for idle lanes and for shared-prefix re-run
    passes whose target position is owned by a shared block (the stored
    value is bit-identical, so skipping the write avoids a spurious
    copy-on-write fork without changing any attention operand), and the
    static ``max_seq`` that sets the width of the gathered window."""
    block_table: jax.Array
    write_mask: jax.Array
    max_seq: int


def _attend_cache(cfg: ModelConfig, p: Dict, h: jax.Array, cache: Dict, pos,
                  window: int, pages: Optional[Pages]
                  ) -> Tuple[jax.Array, Dict]:
    """One attention layer against its cache: a slot cache row per lane
    when ``pages`` is None, else the block-major pages through the block
    table.  GQA/MHA (including the hybrid's shared block) or MLA."""
    if cfg.mla:
        if pages is None:
            return apply_mla_decode(p, h, cache, pos, mla_config(cfg))
        return apply_mla_paged_decode(p, h, cache, pages.block_table, pos,
                                      mla_config(cfg), width=pages.max_seq,
                                      write_mask=pages.write_mask)
    if pages is None:
        return _attn_decode(cfg, p, h, cache, pos, window)
    return _attn_decode_paged(cfg, p, h, cache, pages.block_table, pos,
                              window, pages.max_seq, pages.write_mask)


def decode_step(params: Dict, caches: List, token: jax.Array, pos,
                cfg: ModelConfig, *, pages: Optional[Pages] = None,
                mesh=None) -> Tuple[jax.Array, List]:
    """token (B, 1) int32 -> logits (B, vocab); updates caches.

    ``pos`` is a scalar (all rows decode the same position — the static
    generate path) or a ``(B,)`` int vector of per-row positions (the
    continuous-batching ragged path; each row reads/writes its own cache
    position).  SSM/recurrent layers carry no position and advance one
    step per call either way.

    With ``pages`` None every cache entry holds one row per lane
    (:func:`init_caches`).  With ``pages`` given the caches are the
    engine's (:func:`init_paged_caches`): ``"paged"`` entries are the
    whole page arrays, which every lane reads and writes through its
    block-table row, and ``"slot"`` entries arrive already gathered to
    (B, ...) rows (the engine scatters them back).  Enc-dec decodes from
    slot caches only."""
    x = nn.apply_embedding(params["embed"], token).astype(jnp.dtype(cfg.dtype))
    if cfg.name.startswith("gemma"):
        x = x * (cfg.d_model ** 0.5)   # gemma scales embeddings (as forward)
    new_caches = list(caches)

    if cfg.family in ("dense", "moe"):
        wins = [int(w) for w in window_schedule(cfg)]
        dense_head = cfg.first_dense_layers if cfg.family == "moe" else 0
        for li in range(cfg.n_layers):
            with jax.named_scope(f"layer{li}"):
                p = _dense_layer_params(params, li, dense_head)
                with jax.named_scope("attn"):
                    h = _apply_norm(cfg, p["ln1"], x)
                    a, new_caches[li] = _attend_cache(
                        cfg, p["attn"], h, caches[li], pos, wins[li], pages)
                    x = x + a
                x = _mlp_block(cfg, p, x, li >= dense_head, mesh)

    elif cfg.family == "ssm":
        for li in range(cfg.n_layers):
            p = params["layers"][li]
            h = _apply_norm(cfg, p["ln1"], x)
            y, new_caches[li] = apply_ssm_decode(p["ssm"], h, caches[li],
                                                 ssm_config(cfg))
            x = x + y

    elif cfg.family == "hybrid":
        k = cfg.shared_attn_every
        shared = params["shared_block"]
        g = 0
        for li in range(cfg.n_layers):
            p = params["layers"][li]
            h = _apply_norm(cfg, p["ln1"], x)
            y, new_caches[li] = apply_ssm_decode(p["ssm"], h, caches[li],
                                                 ssm_config(cfg))
            x = x + y
            if (li + 1) % k == 0:
                ci = cfg.n_layers + g
                h = _apply_norm(cfg, shared["ln1"], x)
                a, new_caches[ci] = _attend_cache(
                    cfg, shared["attn"], h, caches[ci], pos, NO_WINDOW, pages)
                x = x + a
                x = x + _apply_mlp(cfg, shared["mlp"],
                                   _apply_norm(cfg, shared["ln2"], x))
                g += 1

    elif cfg.family == "encdec" and pages is None:
        for li in range(cfg.dec_layers):
            p = params["dec_layers"][li]
            h = _apply_norm(cfg, p["ln1"], x)
            a, new_caches[li] = _attn_decode(cfg, p["attn"], h, caches[li],
                                             pos, NO_WINDOW)
            x = x + a
            h = _apply_norm(cfg, p["ln_x"], x)
            q = nn.qkv_project(p["cross"], h, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim)[0]
            Se = caches[li]["ck"].shape[1]
            o = masked_decode_attention(q, caches[li]["ck"], caches[li]["cv"],
                                        jnp.ones((Se,), bool))
            x = x + nn.out_project(p["cross"], o)
            x = x + _apply_mlp(cfg, p["mlp"], _apply_norm(cfg, p["ln2"], x))
    else:
        raise ValueError(f"{cfg.family!r} has no "
                         f"{'paged ' if pages is not None else ''}decode step")

    return _head(cfg, params, x), new_caches
