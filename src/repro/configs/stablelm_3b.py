"""stablelm-3b [dense] — StableLM-3B-4E1T: 32L, d_model=2560, 32 heads of 80
(MHA), SwiGLU d_ff=6912, vocab=50304; LayerNorm (scale and bias, eps 1e-5),
rotary on the first quarter of each head (rope_pct 0.25, theta 10000), an
untied output head, no q/k/v bias, 4096 positions.  long_500k skipped: dense
full attention.

``attn_chunk`` 512: the attention backward recomputes one KV chunk at a
time, and 512-wide chunks leave room for global batch 4 x 4096 tokens
through the FSDP x TP step on a v5e 2x2 (about 13.2 GB a chip at compile
time)."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="stablelm-3b",
    family="dense",
    n_layers=32,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    head_dim=80,
    d_ff=6912,
    vocab=50304,
    rope_theta=10_000.0,
    rope_fraction=0.25,
    norm="layernorm",
    tie_embeddings=False,
    attn_chunk=512,
    supported_shapes=("train_4k", "prefill_32k", "decode_32k"),
    shape_skips={"long_500k": "dense full attention"},
)

REDUCED = CONFIG.replace(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=128, vocab=256, attn_chunk=32, dtype="float32", remat=False)
