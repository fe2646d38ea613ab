"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_pruned_matmul.block_pruned_matmul import block_pruned_matmul
from repro.kernels.block_pruned_matmul.ref import block_pruned_matmul_ref
from repro.kernels.embedding_bag.embedding_bag import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.fm_interaction.fm_interaction import fm_interaction_kernel
from repro.kernels.fm_interaction.ref import fm_interaction_ref
from repro.kernels.int8_matmul.int8_matmul import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref, quantize_activations
from repro.kernels.local_attention.local_attention import local_attention
from repro.kernels.local_attention.ref import local_attention_ref


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 128), (384, 256, 512)])
@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128)])
def test_int8_matmul_shapes(M, K, N, bm, bn, bk):
    key = jax.random.key(M + K + N)
    a = jax.random.normal(key, (M, K))
    w = jax.random.normal(jax.random.key(1), (K, N))
    a_q, a_s = quantize_activations(a)
    w_q, w_s = quantize_activations(w.T)
    w_q = w_q.T
    out = int8_matmul(a_q, w_q, a_s, w_s, bm=bm, bn=bn, bk=bk, interpret=True)
    ref = int8_matmul_ref(a_q, w_q, a_s, w_s)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-4)
    # quantized matmul approximates the f32 one to ~1-2%
    rel = float(jnp.abs(out - a @ w).max() / jnp.abs(a @ w).max())
    assert rel < 0.05


@pytest.mark.parametrize("M,K,N", [(200, 128, 128), (128, 192, 128), (128, 128, 64)])
def test_int8_matmul_rejects_partial_blocks(M, K, N):
    a_q = jnp.ones((M, K), jnp.int8)
    w_q = jnp.ones((K, N), jnp.int8)
    with pytest.raises(ValueError, match="does not tile"):
        int8_matmul(a_q, w_q, jnp.ones((M,)), jnp.ones((N,)), interpret=True)


@pytest.mark.parametrize("M,K,N", [(100, 64, 256), (300, 208, 200), (256, 128, 128)])
def test_quantized_linear_pads_to_blocks(M, K, N):
    """Shapes that do not tile run the kernel on zero-padded operands,
    not the oracle, and still match the oracle."""
    from repro.core.quantization import quantize_weight
    from repro.kernels.int8_matmul.ops import quantized_linear

    x = jax.random.normal(jax.random.key(M), (M, K))
    w_rep = quantize_weight(jax.random.normal(jax.random.key(K), (K, N)))
    out = quantized_linear(x, w_rep)
    x_q, x_s = quantize_activations(x)
    ref = int8_matmul_ref(x_q, w_rep["q"], x_s, w_rep["s"])
    assert out.shape == (M, N)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("density", [0.0, 0.3, 0.7, 1.0])
def test_block_pruned_matmul(density):
    M = K = N = 256
    x = jax.random.normal(jax.random.key(0), (M, K))
    w = jax.random.normal(jax.random.key(1), (K, N))
    mask = (jax.random.uniform(jax.random.key(2), (K // 128, N // 128)) < density)
    out = block_pruned_matmul(x, w, mask.astype(jnp.int32), interpret=True)
    ref = block_pruned_matmul_ref(x, w, mask, block=128)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("L,window", [(256, 64), (512, 128), (512, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_local_attention(causal, L, window, dtype):
    BH, dh = 2, 32
    q = jax.random.normal(jax.random.key(0), (BH, L, dh)).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (BH, L, dh)).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (BH, L, dh)).astype(dtype)
    out = local_attention(q, k, v, window=window, causal=causal, interpret=True)
    ref = local_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        window=window, causal=causal,
    )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("B,nnz,d", [(4, 5, 16), (16, 10, 32), (8, 1, 64)])
def test_embedding_bag(B, nnz, d):
    V = 500
    table = jax.random.normal(jax.random.key(0), (V, d))
    idx = jax.random.randint(jax.random.key(1), (B, nnz), 0, V)
    w = jax.random.uniform(jax.random.key(2), (B, nnz))
    out = embedding_bag(table, idx, w, interpret=True)
    ref = embedding_bag_ref(table, idx, w)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_cached_embedding_bag_matches_ref_exactly(weighted, combiner):
    """The hot-row resident tier (core/caching.py) must be invisible to
    correctness: resident-only, miss-only and mixed lookups all match the
    embedding_bag oracle BITWISE — resident rows are exact copies and the
    reduce path is identical."""
    from repro.core.caching import build_resident_table, cached_embedding_bag, hot_ids
    from repro.data.synthetic import zipf_id_stream

    V, d, B, nnz = 400, 32, 16, 6
    table = jax.random.normal(jax.random.key(3), (V, d))
    stream = zipf_id_stream(4000, V, 1.2, seed=7)
    resident = build_resident_table(table, hot_ids(stream, 64))
    w = jax.random.uniform(jax.random.key(4), (B, nnz)) if weighted else None
    res_ids = np.asarray(hot_ids(stream, 64))
    cases = {
        "resident_only": np.random.default_rng(0).choice(res_ids, (B, nnz)),
        "mixed": np.asarray(stream[: B * nnz]).reshape(B, nnz),
        "miss_only": np.setdiff1d(np.arange(V), res_ids)[: B * nnz].reshape(B, nnz),
    }
    for name, idx in cases.items():
        idx = jnp.asarray(idx.astype(np.int32))
        out = cached_embedding_bag(table, resident, idx, mask=w, combiner=combiner)
        ref = _bag_oracle(table, idx, w, combiner)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref), err_msg=name)


def _bag_oracle(table, idx, w, combiner):
    from repro.models.recsys.embedding import embedding_bag as bag

    return bag(table, idx, mask=w, combiner=combiner)


def test_cached_embedding_bag_int8_table():
    """The int8-quantized table layout dequantizes identically through
    the resident tier (rows cached dequantized) and the fallback path."""
    from repro.core.caching import build_resident_table, cached_embedding_bag, residency_mask

    V, d, B, nnz = 200, 16, 8, 5
    q = jax.random.randint(jax.random.key(5), (V, d), -127, 128, dtype=jnp.int8)
    s = jax.random.uniform(jax.random.key(6), (V,), minval=0.01, maxval=0.1)
    table = {"q": q, "s": s}
    resident = build_resident_table(table, np.arange(32, dtype=np.int64))
    idx = jax.random.randint(jax.random.key(7), (B, nnz), 0, V)
    out = cached_embedding_bag(table, resident, idx)
    ref = _bag_oracle(table, idx, None, "sum")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    hits = residency_mask(resident, idx)
    assert 0 < int(hits.sum()) < idx.size  # genuinely mixed hit/miss


@pytest.mark.parametrize("B,F,k", [(256, 39, 10), (512, 8, 16)])
def test_fm_interaction(B, F, k):
    e = jax.random.normal(jax.random.key(0), (B, F, k))
    out = fm_interaction_kernel(e, bb=min(B, 256), interpret=True)
    ref = fm_interaction_ref(e)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B", [100, 300])
def test_fm_interaction_op_pads_batch(B):
    from repro.kernels.fm_interaction.ops import fm_interaction_op

    e = jax.random.normal(jax.random.key(B), (B, 39, 10))
    out = fm_interaction_op(e)
    assert out.shape == (B,)
    np.testing.assert_allclose(out, fm_interaction_ref(e), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,T,g", [(128, 20, 16), (256, 50, 32)])
def test_augru_kernel(B, T, g):
    from repro.kernels.augru.augru import augru
    from repro.kernels.augru.ref import augru_ref

    zx = jax.random.normal(jax.random.key(0), (B, T, 3 * g))
    wh = jax.random.normal(jax.random.key(1), (g, 3 * g)) * 0.3
    h0 = jax.random.normal(jax.random.key(2), (B, g)) * 0.1
    att = jax.random.uniform(jax.random.key(3), (B, T))
    mask = jax.random.uniform(jax.random.key(4), (B, T)) > 0.2
    out = augru(zx, wh, h0, att, mask, bb=128, interpret=True)
    ref = augru_ref(zx, wh, h0, att, mask)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_augru_matches_dien_model_cell():
    """The kernel recurrence equals the model's _gru_cell-based scan."""
    from repro.kernels.augru.ref import augru_ref
    from repro.models.recsys import dien as dien_mod

    B, T, g, du = 4, 10, 8, 6
    params = {
        "augru_wx": jax.random.normal(jax.random.key(0), (du, 3 * g)) * 0.3,
        "augru_wh": jax.random.normal(jax.random.key(1), (g, 3 * g)) * 0.3,
        "augru_b": jnp.zeros((3 * g,)),
    }
    xs = jax.random.normal(jax.random.key(2), (B, T, du))
    mask = jnp.ones((B, T), bool)
    att = jax.random.uniform(jax.random.key(3), (B, T))
    h_model, _ = dien_mod._run_gru(params, "augru", xs, mask, g, att=att)
    zx = xs @ params["augru_wx"] + params["augru_b"]
    h_kernel = augru_ref(zx, params["augru_wh"], jnp.zeros((B, g)), att, mask)
    np.testing.assert_allclose(h_model, h_kernel, rtol=1e-5, atol=1e-5)
