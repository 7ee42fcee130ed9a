"""Decoder LM assembly: dense GQA / sliding-window / MoE / MLA / SSM /
hybrid families from one composable block vocabulary, with
scan-over-layers + optional remat so the traced HLO contains each distinct
block once (the MaxText pattern — essential for the 512-device dry-run).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.configs.base import ModelConfig
from repro.nn.attention import NO_WINDOW
from repro.nn.core import init_params, stack_specs
from repro.nn.mla import MLAConfig
from repro.nn.moe import MoEConfig
from repro.nn.ssm import SSMConfig


# ---------------------------------------------------------------------------
# config adapters
# ---------------------------------------------------------------------------

def mla_config(cfg: ModelConfig) -> MLAConfig:
    return MLAConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                     kv_lora_rank=cfg.kv_lora_rank,
                     qk_nope_dim=cfg.qk_nope_dim,
                     qk_rope_dim=cfg.qk_rope_dim,
                     v_head_dim=cfg.v_head_dim,
                     rope_theta=cfg.rope_theta)


def moe_config(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(n_experts=cfg.n_experts, top_k=cfg.top_k,
                     d_model=cfg.d_model, d_ff=cfg.moe_d_ff,
                     n_shared=cfg.n_shared_experts,
                     shared_d_ff=cfg.n_shared_experts * cfg.moe_d_ff,
                     capacity_factor=cfg.capacity_factor)


def ssm_config(cfg: ModelConfig) -> SSMConfig:
    return SSMConfig(d_model=cfg.d_model, d_inner=cfg.d_inner,
                     n_heads=cfg.ssm_heads, head_p=cfg.ssm_head_p,
                     n_groups=cfg.ssm_groups, d_state=cfg.ssm_state)


def _norm_spec(cfg: ModelConfig, d: int) -> Dict:
    return (nn.layernorm_spec(d) if cfg.norm == "layernorm"
            else nn.rmsnorm_spec(d))


def _apply_norm(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    return (nn.apply_layernorm(p, x) if cfg.norm == "layernorm"
            else nn.apply_rmsnorm(p, x))


def _mlp_spec(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d_ff = d_ff or cfg.d_ff
    return (nn.gelu_mlp_spec(cfg.d_model, d_ff) if cfg.mlp == "gelu"
            else nn.swiglu_spec(cfg.d_model, d_ff))


def _apply_mlp(cfg: ModelConfig, p: Dict, x: jax.Array,
               tp_axis: Optional[str] = None) -> jax.Array:
    return (nn.apply_gelu_mlp(p, x, tp_axis=tp_axis) if cfg.mlp == "gelu"
            else nn.apply_swiglu(p, x, tp_axis=tp_axis))


# ---------------------------------------------------------------------------
# block specs
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig) -> Dict:
    if cfg.mla:
        return nn.mla_spec(mla_config(cfg))
    spec = nn.gqa_spec(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, cfg.qkv_bias)
    if cfg.qk_norm:
        spec["q_norm"] = nn.rmsnorm_spec(cfg.head_dim, None)
        spec["k_norm"] = nn.rmsnorm_spec(cfg.head_dim, None)
    return spec


def dense_block_spec(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_spec(cfg, cfg.d_model),
            "attn": attn_spec(cfg),
            "ln2": _norm_spec(cfg, cfg.d_model),
            "mlp": _mlp_spec(cfg)}


def moe_block_spec(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_spec(cfg, cfg.d_model),
            "attn": attn_spec(cfg),
            "ln2": _norm_spec(cfg, cfg.d_model),
            "moe": nn.moe_spec(moe_config(cfg))}


def ssm_block_spec(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_spec(cfg, cfg.d_model),
            "ssm": nn.ssm_spec(ssm_config(cfg))}


def model_spec(cfg: ModelConfig) -> Dict:
    spec: Dict = {"embed": nn.embedding_spec(cfg.vocab, cfg.d_model),
                  "final_norm": _norm_spec(cfg, cfg.d_model)}
    if not cfg.tie_embeddings:
        spec["lm_head"] = nn.lm_head_spec(cfg.d_model, cfg.vocab)

    if cfg.family == "dense":
        spec["layers"] = stack_specs(dense_block_spec(cfg), cfg.n_layers)
    elif cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.first_dense_layers
        if cfg.first_dense_layers:
            spec["dense_layers"] = stack_specs(dense_block_spec(cfg),
                                               cfg.first_dense_layers)
        spec["layers"] = stack_specs(moe_block_spec(cfg), n_moe)
    elif cfg.family == "ssm":
        spec["layers"] = stack_specs(ssm_block_spec(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        spec["layers"] = stack_specs(ssm_block_spec(cfg), cfg.n_layers)
        spec["shared_block"] = dense_block_spec(cfg)
    else:
        raise ValueError(f"model_spec: unsupported family {cfg.family}")
    return spec


def init_model(cfg: ModelConfig, key: jax.Array) -> Dict:
    return init_params(model_spec(cfg), key, dtype=jnp.dtype(cfg.dtype))


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def window_schedule(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention window (NO_WINDOW = global).  Gemma-style: every
    ``global_every``-th layer (1-indexed) is global, the rest local.
    Host-side numpy: consumed statically by the decode path and as traced
    scan xs by the training path."""
    if cfg.window is None:
        return np.full((cfg.n_layers,), NO_WINDOW, np.int32)
    idx = np.arange(cfg.n_layers)
    is_global = (idx % cfg.global_every) == (cfg.global_every - 1) \
        if cfg.global_every else np.zeros((cfg.n_layers,), bool)
    return np.where(is_global, NO_WINDOW, cfg.window).astype(np.int32)


def apply_attn(cfg: ModelConfig, p: Dict, x: jax.Array, *,
               window=NO_WINDOW, q_offset: int = 0,
               causal: bool = True, tp_axis: Optional[str] = None
               ) -> jax.Array:
    if cfg.mla:
        return nn.apply_mla(p, x, mla_config(cfg), causal=causal,
                            q_offset=q_offset, chunk=cfg.attn_chunk)
    B, S, _ = x.shape
    # explicit TP (inside a shard_map over tp_axis): the projection weights
    # are head shards, so the local head counts come from the *local* shard
    # shapes; the output projection psums the per-shard partials
    if tp_axis is None:
        n_heads, n_kv = cfg.n_heads, cfg.n_kv_heads
    else:
        n_heads = p["wq"]["w"].shape[1] // cfg.head_dim
        n_kv = p["wk"]["w"].shape[1] // cfg.head_dim
    q, k, v = nn.qkv_project(p, x, n_heads, n_kv, cfg.head_dim)
    if cfg.qk_norm:
        q = nn.apply_rmsnorm(p["q_norm"], q)
        k = nn.apply_rmsnorm(p["k_norm"], k)
    positions = q_offset + jnp.arange(S)
    q = nn.apply_rope(q, positions[None, :], cfg.rope_theta, cfg.rope_dims)
    k = nn.apply_rope(k, positions[None, :], cfg.rope_theta, cfg.rope_dims)
    o = nn.chunked_attention(q, k, v, causal=causal, window=window,
                             chunk=cfg.attn_chunk, q_offset=q_offset)
    return nn.out_project(p, o, tp_axis=tp_axis)


def dense_block(cfg: ModelConfig, p: Dict, x: jax.Array, *,
                window=NO_WINDOW, mesh=None,
                tp_axis: Optional[str] = None) -> jax.Array:
    x = x + apply_attn(cfg, p["attn"], _apply_norm(cfg, p["ln1"], x),
                       window=window, tp_axis=tp_axis)
    x = x + _apply_mlp(cfg, p["mlp"], _apply_norm(cfg, p["ln2"], x),
                       tp_axis=tp_axis)
    return x


def moe_block(cfg: ModelConfig, p: Dict, x: jax.Array, *,
              window=NO_WINDOW, mesh=None,
              tp_axis: Optional[str] = None) -> jax.Array:
    x = x + apply_attn(cfg, p["attn"], _apply_norm(cfg, p["ln1"], x),
                       window=window, tp_axis=tp_axis)
    x = x + nn.apply_moe(p["moe"], _apply_norm(cfg, p["ln2"], x),
                         moe_config(cfg), mesh=mesh)
    return x


def ssm_block(cfg: ModelConfig, p: Dict, x: jax.Array, **_) -> jax.Array:
    return x + nn.apply_ssm(p["ssm"], _apply_norm(cfg, p["ln1"], x),
                            ssm_config(cfg))


_BLOCK_OF = {"dense": dense_block, "moe": moe_block, "ssm": ssm_block}


def stage_forward(cfg: ModelConfig, stacked: Dict, x: jax.Array,
                  windows: Optional[jnp.ndarray] = None,
                  tp_axis: Optional[str] = None) -> jax.Array:
    """Apply a contiguous sub-stack of decoder blocks — one pipeline stage.

    ``stacked`` holds this stage's layers with a leading layer dim (any
    length that the leaves agree on); ``windows`` is the matching slice of
    :func:`window_schedule` for attention families (may be traced — the
    pipeline step slices it by ``axis_index`` inside shard_map).  Runs with
    ``mesh=None``: the pipeline step owns all collectives explicitly.
    ``tp_axis`` names the tensor-parallel mesh axis when the stage runs
    inside a shard_map over a ``pipe × model`` mesh: the attention/MLP
    weights are then head-/column-shards and the blocks psum their partial
    projections over it (see ``repro.nn.layers`` / ``repro.nn.attention``).
    """
    block = _BLOCK_OF.get(cfg.family)
    if block is None:
        raise ValueError(f"stage_forward: unsupported family {cfg.family}")
    if cfg.family == "ssm":
        windows = None   # ssm blocks take no attention window
        if tp_axis is not None:
            raise ValueError("stage_forward: ssm blocks have no TP path")
    return _scan_layers(cfg, block, stacked, x, windows=windows,
                        tp_axis=tp_axis)


def head_forward(params: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm + (tied) unembedding: residual stream -> logits."""
    x = _apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        return nn.unembed(params["embed"], x)
    return nn.apply_lm_head(params["lm_head"], x)


def embed_forward(params: Dict, tokens: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Token embedding (with the gemma sqrt(d) scale) -> residual stream."""
    x = nn.apply_embedding(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.name.startswith("gemma"):
        x = x * (cfg.d_model ** 0.5)   # gemma embeds are sqrt(d)-scaled
    return x


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------

def _stream_layout(cfg: ModelConfig, x: jax.Array, mesh):
    """The residual stream's layout between blocks: rows split over the
    data axes, so that each data shard runs its own rows through every
    layer and the FSDP weights are gathered for it (left free, the
    partitioner may instead keep every row on every chip, split the
    hidden dim over ``data`` and all-reduce the activations of each
    matmul and norm).  With ``seq_parallel`` the sequence is also split
    over the model axis so norms/router/elementwise work is 1/TP of the
    replicated cost and the TP collectives become
    all-gather/reduce-scatter pairs (Megatron-SP).  Rows that do not
    divide the data axes (a microbatch under accumulation, say) are left
    to the partitioner, with a note when the step is traced."""
    if mesh is None:
        return x
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if cfg.seq_parallel and "model" in mesh.axis_names:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, P(dp, "model", None)))
    n = math.prod(mesh.shape[a] for a in dp)
    if not dp or x.shape[0] % n:
        if dp:
            print(f"[model] {x.shape[0]} rows do not divide the data axes "
                  f"({n}): the residual stream's layout is left to the "
                  f"partitioner")
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(dp, None, None)))


def _scan_layers(cfg: ModelConfig, block, stacked: Dict, x: jax.Array,
                 windows: Optional[jnp.ndarray] = None,
                 mesh=None, tp_axis: Optional[str] = None) -> jax.Array:
    body = functools.partial(block, cfg, mesh=mesh)
    if tp_axis is not None:   # ssm_block has no tp_axis kwarg; only bind
        body = functools.partial(body, tp_axis=tp_axis)  # it when in use

    def scan_fn(carry, xs):
        carry = _stream_layout(cfg, carry, mesh)
        if windows is not None:
            layer_p, win = xs
            out = body(layer_p, carry, window=win)
        else:
            out = body(xs, carry)
        return out, None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        scan_fn = jax.checkpoint(scan_fn, policy=policy)
    xs = (stacked, windows) if windows is not None else stacked
    x, _ = jax.lax.scan(scan_fn, x, xs)
    return x


def forward(params: Dict, tokens: jax.Array, cfg: ModelConfig,
            mesh=None) -> jax.Array:
    """tokens (B, S) -> logits (B, S, vocab).  Works for every decoder
    family; whisper lives in repro.models.encdec."""
    x = embed_forward(params, tokens, cfg)

    if cfg.family == "dense":
        x = _scan_layers(cfg, dense_block, params["layers"], x,
                         windows=window_schedule(cfg), mesh=mesh)
    elif cfg.family == "moe":
        if cfg.first_dense_layers:
            x = _scan_layers(cfg, dense_block, params["dense_layers"], x,
                             windows=window_schedule(cfg)
                             [: cfg.first_dense_layers], mesh=mesh)
        x = _scan_layers(cfg, moe_block, params["layers"], x,
                         windows=window_schedule(cfg)
                         [cfg.first_dense_layers:], mesh=mesh)
    elif cfg.family == "ssm":
        x = _scan_layers(cfg, ssm_block, params["layers"], x, mesh=mesh)
    elif cfg.family == "hybrid":
        x = _hybrid_forward(params, x, cfg, mesh)
    else:
        raise ValueError(cfg.family)

    return head_forward(params, x, cfg)


def _hybrid_forward(params: Dict, x: jax.Array, cfg: ModelConfig,
                    mesh) -> jax.Array:
    """Zamba2: scan groups of ``shared_attn_every`` Mamba2 layers, applying
    the single shared attention+MLP block after each group."""
    k = cfg.shared_attn_every
    assert cfg.n_layers % k == 0, (cfg.n_layers, k)
    n_groups = cfg.n_layers // k
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), params["layers"])
    shared = params["shared_block"]

    def group_fn(carry, group_params):
        def inner(c, layer_p):
            return ssm_block(cfg, layer_p, c), None
        h, _ = jax.lax.scan(inner, carry, group_params)
        h = dense_block(cfg, shared, h, mesh=mesh)
        return h, None

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn,
                                  policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(group_fn, x, grouped)
    return x
