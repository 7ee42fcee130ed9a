"""GQA attention with online-softmax KV chunking.

One implementation serves every attention arch here:
  * training / prefill: ``chunked_attention`` — lax.scan over KV chunks with
    a running (max, sum, acc), so activation memory is O(S·chunk) instead of
    O(S²) and the HLO stays compact for the 512-device dry-run;
  * decode: ``decode_attention`` — one query against the KV cache (masked to
    the current position / sliding window).  Under pjit the cache may be
    sharded on heads or on sequence; the SPMD partitioner inserts the
    partial-softmax combine collectives for the latter.

Sliding windows are expressed as a (possibly traced, per-layer) scalar with
``NO_WINDOW`` meaning global — one code path covers gemma-style 5:1
local:global stacks inside a scan over layers.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.nn.core import dense

NO_WINDOW = 1 << 30
_NEG = -1e30


def gqa_spec(d_model: int, n_heads: int, n_kv: int, head_dim: int,
             qkv_bias: bool = False) -> Dict:
    return {
        "wq": dense(d_model, n_heads * head_dim, ("embed", "heads"),
                    bias=qkv_bias),
        "wk": dense(d_model, n_kv * head_dim, ("embed", "kv_heads"),
                    bias=qkv_bias),
        "wv": dense(d_model, n_kv * head_dim, ("embed", "kv_heads"),
                    bias=qkv_bias),
        "wo": dense(n_heads * head_dim, d_model, ("heads", "embed")),
    }


def qkv_project(p: Dict, x: jax.Array, n_heads: int, n_kv: int,
                head_dim: int):
    from repro.nn.core import apply_dense
    B, S, _ = x.shape
    q = apply_dense(p["wq"], x).reshape(B, S, n_heads, head_dim)
    k = apply_dense(p["wk"], x).reshape(B, S, n_kv, head_dim)
    v = apply_dense(p["wv"], x).reshape(B, S, n_kv, head_dim)
    return q, k, v


def out_project(p: Dict, o: jax.Array,
                tp_axis: Optional[str] = None) -> jax.Array:
    """``tp_axis`` (explicit tensor parallelism inside a ``shard_map``):
    ``o`` holds this rank's head shard, ``wo`` the matching row shard, and
    the partial output projection is assembled by a ``psum``."""
    from repro.nn.core import apply_dense
    B, S, H, D = o.shape
    y = apply_dense(p["wo"], o.reshape(B, S, H * D))
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      window=NO_WINDOW,
                      chunk: int = 1024,
                      q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H % KH == 0.

    Online softmax over KV chunks (flash-attention recurrence in XLA ops —
    the Pallas kernel version of the same math lives in repro.kernels).
    The backward recomputes each chunk's probabilities rather than storing
    them, so its memory is O(Sq·chunk) for one chunk at a time.
    """
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                      # may differ from D (MLA)
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    chunk = min(chunk, Sk)
    assert Sk % chunk == 0, (Sk, chunk)
    n_chunks = Sk // chunk

    qf = q.astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)

    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, KH, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, KH, Dv), 1, 0)

    # Under autodiff the chunk body is recomputed in the backward from
    # (q, the chunk's k and v, the carried running max and sum): only the
    # small (m, l, acc) carry is saved per chunk, never the chunk's
    # (Sq x chunk) float32 logits and probabilities.  The forward's
    # arithmetic is the same either way.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        m, ell, acc = carry
        kb, vb, cidx = xs
        k_pos = cidx * chunk + jnp.arange(chunk)
        # (B, KH, G, Sq, C)
        logits = jnp.einsum("bqhgd,bchd->bhgqc",
                            qf.reshape(B, Sq, KH, G, D).transpose(0, 1, 2, 3, 4),
                            kb.astype(jnp.float32))
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        p_ = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = ell * alpha + p_.sum(axis=-1, keepdims=True)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p_, vb.astype(jnp.float32))
        acc_new = acc * alpha + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, G, Sq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, Dv), jnp.float32)
    (m, ell, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(ell, 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def masked_decode_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, mask: jax.Array, *,
                            scale: Optional[float] = None) -> jax.Array:
    """One-query attention against a gathered cache under an explicit mask.

    q: (B, 1, H, D); caches: (B, S, KH, Dv); mask: (S,) shared across rows
    or (B, S) per-row (ragged positions).  This is THE decode softmax —
    the dense slot path and the paged block-table path both call it, so
    their outputs are bit-identical whenever the gathered (k, v, mask)
    triples match.  Masked positions contribute exactly 0.0 regardless of
    the cache values there (``where`` replaces their logits with -1e30 and
    ``exp(-1e30 - m)`` underflows), so garbage in never-written or
    clamped-gather positions cannot perturb the output."""
    B, _, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bqhgd,bshd->bhgqs", qf.reshape(B, 1, KH, G, D),
                        k_cache.astype(jnp.float32))
    maskb = (mask[None, None, None, None] if mask.ndim == 1
             else mask[:, None, None, None, :])
    logits = jnp.where(maskb, logits, _NEG)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    ell = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhgqs,bshd->bhgqd", p, v_cache.astype(jnp.float32)) / ell
    return o.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, Dv).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window=NO_WINDOW,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, 1, H, D); caches: (B, S, KH, D); pos: scalar index of the
    current token.  One masked softmax over the cache (linear per step)."""
    S = k_cache.shape[1]
    k_pos = jnp.arange(S)
    mask = (k_pos <= pos) & (k_pos > pos - window)
    return masked_decode_attention(q, k_cache, v_cache, mask, scale=scale)


def update_cache(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """Write ``new`` (B, 1, KH, D) into (B, S, KH, D) at ``pos``.

    ``pos`` may be a scalar (every batch row writes the same position —
    the static-batch generate path, via dynamic_update_slice touching
    O(slice) bytes) or a ``(B,)`` vector (each row writes its own
    position — the continuous-batching ragged decode path, via a
    per-row scatter)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim >= 1:
        rows = jnp.arange(cache.shape[0])
        return cache.at[rows, pos].set(new[:, 0].astype(cache.dtype))
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (zero, pos, zero, zero))


# ---------------------------------------------------------------------------
# paged KV: physical pages indexed through per-request block tables
# ---------------------------------------------------------------------------
#
# The paged layout stores KV block-major — ``pages`` is
# ``(num_blocks, block_size, *rest)`` shared by every request — and each
# request addresses its sequence through a row of physical block ids
# (``block_table``: (B, blocks_per_slot) int32, padded with 0 past the
# granted blocks; reads there are masked, writes suppressed).  Absolute
# position ``p`` of row ``b`` lives at page slot
# ``(block_table[b, p // block_size], p % block_size)``.


def paged_flat_index(block_table: jax.Array, pos: jax.Array,
                     block_size: int) -> jax.Array:
    """(B,) flattened page-slot index of absolute position ``pos`` per row
    (into ``pages.reshape(num_blocks * block_size, ...)``)."""
    pos = jnp.asarray(pos, jnp.int32)
    rows = jnp.arange(block_table.shape[0])
    blk = block_table[rows, pos // block_size]
    return blk * block_size + pos % block_size


def paged_update_cache(pages: jax.Array, new: jax.Array,
                       block_table: jax.Array, pos: jax.Array, *,
                       write_mask: Optional[jax.Array] = None) -> jax.Array:
    """Write ``new`` (B, 1, *rest) into block-major ``pages``
    (num_blocks, block_size, *rest) at per-row absolute positions ``pos``.

    One masked scatter: lanes with ``write_mask`` False (idle lanes padded
    into the fixed-width batch, or shared-prefix re-run passes whose
    target position is owned by a shared block) are routed to an
    out-of-range index and dropped (``mode="drop"``) — no scratch row, no
    duplicate writes, safe under buffer donation.  Active lanes write
    distinct page slots by construction (each writes into a block its
    request owns exclusively — copy-on-write forks shared blocks first)."""
    N, bs = pages.shape[:2]
    flat = paged_flat_index(block_table, pos, bs)
    if write_mask is not None:
        flat = jnp.where(write_mask, flat, N * bs)
    rest = pages.shape[2:]
    out = pages.reshape(N * bs, *rest).at[flat].set(
        new[:, 0].astype(pages.dtype), mode="drop")
    return out.reshape(N, bs, *rest)


def gather_pages(pages: jax.Array, block_table: jax.Array,
                 width: int) -> jax.Array:
    """Gather absolute positions ``[0, width)`` of every row:
    (B, width, *rest).  ``width`` may be below the table's coverage
    (``max_seq`` not a multiple of ``block_size``) — the tail page slots
    are simply never materialized into the attention operand, keeping the
    contraction width identical to the dense layer's cache."""
    B, nb = block_table.shape
    bs = pages.shape[1]
    g = pages[block_table].reshape(B, nb * bs, *pages.shape[2:])
    return g[:, :width]


def gather_page_window(pages: jax.Array, block_table: jax.Array,
                       pos: jax.Array, width: int) -> jax.Array:
    """Gather the trailing window — absolute positions
    ``pos - width + 1 .. pos`` per row — as (B, width, *rest).

    This reconstructs exactly what the dense sliding-window ring buffer
    holds after its shift-and-append, so windowed layers stay bit-exact
    under paging.  Negative positions clamp to 0; callers mask them
    (``k_positions >= 0``), and masked garbage contributes exactly 0."""
    N, bs = pages.shape[:2]
    pos = jnp.asarray(pos, jnp.int32)
    abs_pos = jnp.maximum(pos[:, None] + jnp.arange(width)[None, :]
                          - (width - 1), 0)                      # (B, W)
    blk = jnp.take_along_axis(block_table, abs_pos // bs, axis=1)
    flat = blk * bs + abs_pos % bs
    return pages.reshape(N * bs, *pages.shape[2:])[flat]


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           pos: jax.Array, *, window: int = NO_WINDOW,
                           width: Optional[int] = None,
                           scale: Optional[float] = None) -> jax.Array:
    """One-token attention through the block table.

    q: (B, 1, H, D); k/v pages: (num_blocks, block_size, KH * D), every
    KV head of a position side by side in one row, split into
    (B, S, KH, D) only after the gather; block_table: (B, blocks_per_slot)
    physical ids; pos: (B,) per-row ragged positions.
    ``window``/``width`` must be static ints (they pick the gather shape —
    one compile per layer geometry): bounded windows gather the
    ``width``-sized trailing window, global attention gathers absolute
    positions ``[0, width)``.  The gathered operands — and hence the
    outputs — are bit-identical to the dense slot path's whenever
    ``width`` matches the dense layer's cache length."""
    B, _, _, D = q.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    if window < NO_WINDOW and width is not None and width <= window:
        S = width
        k_g = gather_page_window(k_pages, block_table, pos, S)
        v_g = gather_page_window(v_pages, block_table, pos, S)
        mask = (positions - (S - 1) + jnp.arange(S)[None]) >= 0
    else:
        S = width if width is not None \
            else block_table.shape[1] * k_pages.shape[1]
        k_g = gather_pages(k_pages, block_table, S)
        v_g = gather_pages(v_pages, block_table, S)
        k_positions = jnp.arange(S)[None]
        mask = (k_positions <= positions) & (k_positions > positions - window)
    return masked_decode_attention(q, k_g.reshape(B, S, -1, D),
                                   v_g.reshape(B, S, -1, D), mask,
                                   scale=scale)
