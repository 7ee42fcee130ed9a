"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in repro.kernels.ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import cases, ref
from repro.kernels.bmm import bmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_ff import fused_ff
from repro.kernels.matmul_leakyrelu import matmul_leakyrelu
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.softmax import softmax
from repro.kernels.ssd import ssd

_ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.5).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 128, 256, 128, 128, 128),
    (128, 256, 128, 64, 128, 64),
])
def test_matmul_leakyrelu(dtype, m, n, k, bm, bn, bk):
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(ka, (m, k), dtype), _rand(kb, (k, n), dtype)
    got = matmul_leakyrelu(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.matmul_leakyrelu(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_ATOL[dtype], rtol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bmm(dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(1))
    a, b = _rand(ka, (3, 128, 128), dtype), _rand(kb, (3, 128, 128), dtype)
    got = bmm(a, b, bm=64, bn=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.bmm(a, b), np.float32),
                               atol=_ATOL[dtype], rtol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ff(dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = _rand(ks[0], (128, 128), dtype)
    wg, wu = _rand(ks[1], (128, 128), dtype), _rand(ks[2], (128, 128), dtype)
    got = fused_ff(x, wg, wu, bm=64, bn=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.fused_ff(x, wg, wu), np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=3e-2)


@pytest.mark.parametrize("rows,cols,br", [(512, 4096, 8), (64, 1024, 16)])
def test_softmax_paper_config(rows, cols, br):
    x = _rand(jax.random.PRNGKey(3), (rows, cols), jnp.float32) * 4
    got = softmax(x, br=br, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.softmax(x)),
                               atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(dtype):
    x = _rand(jax.random.PRNGKey(4), (64, 2048), dtype)
    g = _rand(jax.random.PRNGKey(5), (2048,), dtype) + 1.0
    got = rmsnorm(x, g, br=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.rmsnorm(x, g), np.float32),
                               atol=_ATOL[dtype], rtol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,d,bq,bk", [(256, 64, 128, 128), (512, 32, 128, 256)])
def test_flash_attention(causal, s, d, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(ks[0], (1, 4, s, d), jnp.float32)
    k = _rand(ks[1], (1, 4, s, d), jnp.float32)
    v = _rand(ks[2], (1, 4, s, d), jnp.float32)
    got = flash_attention(q, k, v, bq=bq, bk=bk, causal=causal,
                          interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_flash_attention_paper_config():
    """Table 2: B=1, n_head=4, seq_len=4096, d_head=32 (scaled down 4x in
    sequence to keep interpret-mode CI time sane)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (1, 4, 1024, 32), jnp.bfloat16)
    k = _rand(ks[1], (1, 4, 1024, 32), jnp.bfloat16)
    v = _rand(ks[2], (1, 4, 1024, 32), jnp.bfloat16)
    got = flash_attention(q, k, v, bq=128, bk=128, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


@pytest.mark.parametrize("chunk", [32, 64])
def test_ssd_vs_scan_oracle(chunk):
    BH, S, P, N = 2, 128, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    x = _rand(ks[0], (BH, S, P), jnp.float32)
    a = -jnp.abs(_rand(ks[1], (BH, S), jnp.float32)) * 0.2
    b = _rand(ks[2], (BH, S, N), jnp.float32)
    c = _rand(ks[3], (BH, S, N), jnp.float32)
    got = ssd(x, a, b, c, chunk=chunk, interpret=True)
    want = ref.ssd(x, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("name", cases.BUILTIN)
def test_check_case_matches_ref_in_interpret_mode(name):
    """The cases chip_smoke.py runs on the chip agree with their oracles
    here, so a miss there points at the chip, not the case."""
    case = cases.kernel_case(name, interpret=True)
    args = case.make(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        want = case.ref(*args)
    assert cases.relative_error(case.run(*args), want) <= cases.REL_TOL
