"""The mechanisms StableLM-3B-4E1T needs, at small sizes on the CPU:
partial rotary, the attention backward that recomputes each KV chunk, and
the reduced model's paged decode against its own forward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.nn.attention import NO_WINDOW, chunked_attention
from repro.nn.rope import apply_rope
from repro.serve import ServeEngine


def _full_rope(x, positions, theta):
    """Rotate-half over every dim, as apply_rope computed before partial
    rotary existed."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=jnp.float32)
                             / (d // 2)))
    ang = (positions[..., None].astype(jnp.float32) * freqs)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def test_partial_rotary_rotates_exactly_the_first_dims():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(12) + 5, (2, 12))
    out = apply_rope(x, pos, 10000.0, rot_dims=4)
    # the tail passes through bit for bit
    np.testing.assert_array_equal(np.asarray(out[..., 4:]),
                                  np.asarray(x[..., 4:]))
    # the head is a full rotary of its 4 dims (inv_freq over r, not D)
    np.testing.assert_allclose(np.asarray(out[..., :4]),
                               np.asarray(_full_rope(x[..., :4], pos,
                                                     10000.0)),
                               rtol=1e-6, atol=1e-6)
    # a rotation: each (i, i + r/2) pair keeps its length
    def pair_norms(a):
        return np.hypot(np.asarray(a[..., :2]), np.asarray(a[..., 2:4]))
    np.testing.assert_allclose(pair_norms(out), pair_norms(x), rtol=1e-5)


@pytest.mark.parametrize("rot", [None, 16])
def test_rotary_over_the_whole_head_is_unchanged(rot):
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, 16),
                          jnp.bfloat16)
    pos = jnp.arange(9)[None]
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, pos, 500.0, rot_dims=rot)),
        np.asarray(_full_rope(x, pos, 500.0).astype(jnp.bfloat16)))


def test_config_rope_dims():
    cfg = get_config("stablelm-3b")
    assert (cfg.rope_dims, cfg.norm, cfg.tie_embeddings) == (20, "layernorm",
                                                             False)
    assert get_config("qwen1.5-4b").rope_dims == 128
    assert get_config("stablelm-3b", reduced=True).rope_dims == 4


def _plain_attention(q, k, v, causal, window, q_offset):
    """Masked softmax attention over the whole score matrix."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    qp = q_offset + jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = kp > qp - window
    if causal:
        mask = mask & (kp <= qp)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (Sq, Sk, H, KH, causal, window, q_offset, chunk)
ATTN_CASES = {
    "causal": (32, 32, 4, 4, True, NO_WINDOW, 0, 8),
    "windowed": (32, 32, 4, 4, True, 6, 0, 8),
    "gqa": (32, 32, 8, 2, True, NO_WINDOW, 0, 16),
    "offset": (8, 32, 4, 2, True, NO_WINDOW, 24, 8),
}


@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_chunked_attention_gradients_match_plain_attention(case):
    """Float32 throughout; tolerance 2e-5 relative to the largest
    gradient of each input."""
    Sq, Sk, H, KH, causal, window, off, chunk = ATTN_CASES[case]
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(kq, (2, Sq, H, 16))
    k = jax.random.normal(kk, (2, Sk, KH, 16))
    v = jax.random.normal(kv, (2, Sk, KH, 16))
    g = jax.random.normal(kg, (2, Sq, H, 16))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * g)

    got = jax.grad(loss(lambda q, k, v: chunked_attention(
        q, k, v, causal=causal, window=window, chunk=chunk, q_offset=off)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _plain_attention(
        q, k, v, causal, window, off)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, rtol=0)


def test_chunked_attention_backward_saves_no_chunk_probabilities():
    """Under autodiff the residuals kept for the backward hold no
    (Sq x chunk) float32 tensor per chunk: the chunk body is recomputed."""
    B, S, H, D, chunk = 1, 64, 2, 8, 16
    q = k = v = jnp.ones((B, S, H, D))
    f = lambda q, k, v: chunked_attention(q, k, v, chunk=chunk)  # noqa: E731
    _, res = jax.vjp(f, q, k, v)
    shapes = [tuple(x.shape) for x in jax.tree.leaves(res)]
    n_chunks = S // chunk
    assert not [s for s in shapes if s and s[-1] == chunk and S in s
                and n_chunks in s], shapes


def test_paged_decode_of_reduced_stablelm_agrees_with_its_forward():
    """Greedy tokens from the paged engine are the forward pass's argmax
    at every generated position (teacher-forced over prompt + output),
    with LayerNorm, partial rotary and the untied head."""
    cfg = get_config("stablelm-3b", reduced=True)
    params = lm.init_model(cfg, jax.random.PRNGKey(3))
    own = jax.tree.map(jnp.copy, params)
    engine = ServeEngine.from_config(cfg, params=own, max_batch=2,
                                     max_seq=40, block_size=8,
                                     prefill_chunk=2)
    rng = np.random.default_rng(5)
    jobs = [(rng.integers(0, cfg.vocab, n).tolist(), m)
            for n, m in ((9, 12), (17, 8), (5, 14))]
    reqs = [engine.submit(p, m) for p, m in jobs]
    engine.run()
    for req, (prompt, m) in zip(reqs, jobs):
        assert len(req.output) == m
        seq = jnp.asarray([prompt + req.output[:-1]], jnp.int32)
        logits = lm.forward(params, seq, cfg)[0]
        want = np.asarray(jnp.argmax(logits[len(prompt) - 1:], axis=-1))
        assert req.output == want.tolist()
