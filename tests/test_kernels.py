"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (assignment deliverable c)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ir import ell_storage_width
from repro.kernels import ref
from repro.kernels.batched_gemm import batched_gemm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.rglru import rglru_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rwkv6 import rwkv6_scan
from repro.kernels.spmv import csr_to_ell, spmv_csr, spmv_ell


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == np.float16 or \
        dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (130, 70, 250), (256, 512, 128),
                                   (33, 129, 65), (1, 1, 1)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_sweep(rng, m, k, n, dtype):
    a = rng.standard_normal((m, k), dtype=np.float32).astype(dtype)
    b = rng.standard_normal((k, n), dtype=np.float32).astype(dtype)
    out = matmul(a, b, bm=64, bn=128, bk=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.matmul(a, b),
                                                np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,m,k,n,vec", [
    (12, 16, 24, 32, True), (3, 130, 70, 150, False), (1, 8, 8, 8, True),
    (7, 64, 64, 64, None)])
def test_batched_gemm_sweep(rng, b, m, k, n, vec):
    a = rng.standard_normal((b, m, k), dtype=np.float32)
    bb = rng.standard_normal((b, k, n), dtype=np.float32)
    out = batched_gemm(a, bb, vectorize_batch=vec, bm=32, bn=64, bk=32,
                       interpret=True)
    np.testing.assert_allclose(out, ref.batched_gemm(a, bb), rtol=2e-4,
                               atol=2e-4)


def _random_csr(rng, n, m, density):
    dense = np.where(rng.random((n, m)) < density,
                     rng.standard_normal((n, m)).astype(np.float32), 0.0)
    indptr = np.zeros(n + 1, np.int32)
    vals, cols = [], []
    for i in range(n):
        nz = np.nonzero(dense[i])[0]
        vals.extend(dense[i, nz])
        cols.extend(nz)
        indptr[i + 1] = indptr[i] + len(nz)
    return (indptr, np.asarray(cols, np.int32),
            np.asarray(vals, np.float32), dense)


@pytest.mark.parametrize("n,m,density,rb,rw", [
    (100, 80, 0.05, 32, 8), (257, 129, 0.02, 64, 8), (64, 64, 0.5, 16, 32),
    (50, 50, 0.0, 8, 8)])
def test_spmv_sweep(rng, n, m, density, rb, rw):
    indptr, cols, vals, dense = _random_csr(rng, n, m, density)
    x = rng.standard_normal(m).astype(np.float32)
    y = spmv_csr(indptr, cols, vals, x, n_rows=n, row_block=rb,
                 row_width=rw, interpret=True)
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


def test_spmv_ell_reuse_and_jit(rng):
    indptr, cols, vals, dense = _random_csr(rng, 64, 48, 0.1)
    x = rng.standard_normal(48).astype(np.float32)
    ell = csr_to_ell(indptr, cols, vals, 64, 48)
    f = jax.jit(lambda e, xx: spmv_ell(e, xx, row_block=16, row_width=8,
                                       interpret=True))
    np.testing.assert_allclose(f(ell, x), dense @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hq,hkv,sq,skv,causal,window", [
    (4, 4, 64, 64, True, None), (4, 2, 100, 100, True, None),
    (8, 1, 64, 64, True, 17), (4, 4, 32, 96, False, None),
    (6, 2, 65, 65, True, 33)])
def test_flash_attention_sweep(rng, hq, hkv, sq, skv, causal, window):
    q = rng.standard_normal((2, hq, sq, 32), dtype=np.float32)
    k = rng.standard_normal((2, hkv, skv, 32), dtype=np.float32)
    v = rng.standard_normal((2, hkv, skv, 32), dtype=np.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=32,
                          bkv=32, interpret=True)
    exp = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


def test_flash_attention_softcap(rng):
    q = rng.standard_normal((1, 2, 48, 16), dtype=np.float32)
    k = rng.standard_normal((1, 2, 48, 16), dtype=np.float32)
    v = rng.standard_normal((1, 2, 48, 16), dtype=np.float32)
    out = flash_attention(q, k, v, causal=True, logit_softcap=30.0,
                          bq=16, bkv=16, interpret=True)
    exp = ref.attention(q, k, v, causal=True, logit_softcap=30.0)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


def test_chunked_attention_matches_ref(rng):
    from repro.kernels.chunked import chunked_attention
    q = rng.standard_normal((2, 4, 300, 32), dtype=np.float32)
    k = rng.standard_normal((2, 2, 300, 32), dtype=np.float32)
    v = rng.standard_normal((2, 2, 300, 32), dtype=np.float32)
    for kw in ({"causal": True}, {"causal": True, "window": 64},
               {"causal": False}):
        out = chunked_attention(q, k, v, q_chunk=128, kv_chunk=64, **kw)
        exp = ref.attention(q, k, v, **kw)
        np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,chunk", [(16, 16), (37, 16), (64, 32)])
def test_rwkv6_sweep(rng, t, chunk):
    B, H, K, V = 2, 3, 8, 16
    r = rng.standard_normal((B, t, H, K), dtype=np.float32) * 0.5
    k = rng.standard_normal((B, t, H, K), dtype=np.float32) * 0.5
    v = rng.standard_normal((B, t, H, V), dtype=np.float32) * 0.5
    w = 0.5 + 0.4 * rng.random((B, t, H, K)).astype(np.float32)
    u = rng.standard_normal((H, K), dtype=np.float32) * 0.1
    out = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    exp = ref.rwkv6_scan(r, k, v, w, u)[0]
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,d,chunk,dblock", [(16, 32, 8, 32),
                                              (29, 48, 8, 16),
                                              (64, 128, 32, 64)])
def test_rglru_sweep(rng, t, d, chunk, dblock):
    B = 2
    x = rng.standard_normal((B, t, d), dtype=np.float32)
    r = rng.standard_normal((B, t, d), dtype=np.float32)
    i = rng.standard_normal((B, t, d), dtype=np.float32)
    la = rng.standard_normal(d).astype(np.float32)
    out = rglru_scan(x, r, i, la, chunk=chunk, d_block=dblock,
                     interpret=True)
    exp = ref.rglru_scan(x, r, i, la)[0]
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(5, 64), (3, 33, 128), (1, 1, 256)])
def test_rmsnorm_sweep(rng, shape):
    x = rng.standard_normal(shape, dtype=np.float32)
    w = rng.standard_normal(shape[-1]).astype(np.float32)
    out = rmsnorm(x, w, block_rows=4, interpret=True)
    np.testing.assert_allclose(out, ref.rmsnorm(x, w), rtol=2e-5,
                               atol=2e-5)


def test_kernel_grads_via_custom_vjp(rng):
    """Kernel forward + oracle-derived backward must match oracle grads."""
    from repro.kernels import ops as kops
    from repro.core.options import CompileOptions, use_options
    a = rng.standard_normal((32, 16), dtype=np.float32)
    b = rng.standard_normal((16, 24), dtype=np.float32)

    def loss_kernel(a, b):
        with use_options(CompileOptions(target="pallas", interpret=True,
                                        prefer_library=False)):
            from repro.core.registry import dispatch
            return jnp.sum(dispatch("kk.gemm", target="pallas")(
                a, b, interpret=True) ** 2)

    def loss_ref(a, b):
        return jnp.sum(ref.matmul(a, b) ** 2)

    # gemm_pallas wraps a custom_vjp; grads must agree with the oracle
    from repro.kernels.ops import gemm_pallas
    g1 = jax.grad(lambda a: jnp.sum(gemm_pallas(a, b, interpret=True)**2))(a)
    g2 = jax.grad(lambda a: jnp.sum(ref.matmul(a, b) ** 2))(a)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hq,hkv,s,window", [
    (4, 4, 100, None), (8, 2, 128, None), (4, 1, 90, 33), (2, 2, 64, 16)])
def test_decode_attention_kernel_sweep(rng, hq, hkv, s, window):
    from repro.kernels.decode_attention import decode_attention
    B, D = 3, 32
    q = rng.standard_normal((B, hq, D), dtype=np.float32)
    k = rng.standard_normal((B, hkv, s, D), dtype=np.float32)
    v = rng.standard_normal((B, hkv, s, D), dtype=np.float32)
    lengths = np.asarray(rng.integers(1, s + 1, B), np.int32)
    out = decode_attention(q, k, v, jnp.asarray(lengths), window=window,
                           bs=32, interpret=True)
    exp = ref.decode_attention(q, k, v, jnp.asarray(lengths),
                               window=window)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


def _csr_of(lens, rng, dtype=np.float32):
    lens = np.asarray(lens, np.int64)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    nnz = int(indptr[-1])
    return (indptr, rng.integers(0, 97, nnz).astype(np.int32),
            rng.standard_normal(nnz).astype(dtype))


def _ell_rows(indptr, indices, values, width):
    """The padded ELL layout built one row at a time."""
    n = len(indptr) - 1
    vals = np.zeros((n, width), values.dtype)
    cols = np.zeros((n, width), np.int32)
    valid = np.zeros((n, width), bool)
    for r in range(n):
        k = min(int(indptr[r + 1] - indptr[r]), width)
        lo = int(indptr[r])
        vals[r, :k] = values[lo:lo + k]
        cols[r, :k] = indices[lo:lo + k]
        valid[r, :k] = True
    return vals, cols, valid


ELL_CASES = {                      # row lengths, max_nnz_row, values dtype
    "empty-rows": ([0, 4, 0, 0, 7, 0, 1, 0], 7, np.float32),
    "row-at-max": ([3, 16, 2, 5, 16, 1], 16, np.float32),
    "last-row-longest": ([2, 0, 3, 1, 4, 0, 2, 23], 23, np.float32),
    "max-not-multiple-of-8": ([13, 2, 0, 9, 13, 4, 1], 13, np.float32),
    "nnz-zero": ([0, 0, 0, 0, 0], 0, np.float32),
    "zero-rows": ([], 3, np.float32),
    "bf16-values": ([5, 0, 11, 2], 11, jnp.bfloat16),
}


@pytest.mark.parametrize("mode", ["jit-static", "eager-none"])
@pytest.mark.parametrize("case", sorted(ELL_CASES))
def test_csr_to_ell_matches_rowwise(rng, case, mode):
    """csr_to_ell builds exactly the per-row ELL: values, indices and
    mask bit for bit, the storage width, dtypes and padding."""
    lens, max_nnz_row, dtype = ELL_CASES[case]
    indptr, indices, values = _csr_of(lens, rng, dtype)
    n = len(lens)
    if mode == "jit-static":
        ell = jax.jit(lambda ip, ind, val: csr_to_ell(
            ip, ind, val, n, 97, max_nnz_row=max_nnz_row))(
            indptr, indices, values)
    else:
        ell = csr_to_ell(indptr, indices, values, n, 97)
    width = ell_storage_width(max_nnz_row if mode == "jit-static"
                              else max(lens, default=0))
    vals, cols, valid = _ell_rows(indptr, indices, values, width)
    assert ell.values.shape == ell.indices.shape == ell.valid.shape \
        == (n, width)
    assert ell.values.dtype == values.dtype
    assert ell.indices.dtype == jnp.int32 and ell.valid.dtype == jnp.bool_
    np.testing.assert_array_equal(
        np.asarray(ell.values).view(f"u{values.itemsize}"),
        vals.view(f"u{values.itemsize}"))
    np.testing.assert_array_equal(np.asarray(ell.indices), cols)
    np.testing.assert_array_equal(np.asarray(ell.valid), valid)
    assert int(ell.n_rows) == n and int(ell.n_cols) == 97
    assert float(ell.nnz_mean) == pytest.approx(
        len(values) / n if n and len(values) else 0.0)


def test_csr_to_ell_gathers_windows_not_slots(rng):
    """The conversion copies one window per row: no gather from the
    values or indices reads a single element per index (2,000 rows,
    width 64)."""
    lens = rng.integers(0, 65, 2000)
    lens[-1] = 64
    indptr, indices, values = _csr_of(lens, rng)
    hlo = jax.jit(lambda ip, ind, val: csr_to_ell(
        ip, ind, val, 2000, 97, max_nnz_row=64)).lower(
        indptr, indices, values).as_text()
    nnz = len(values)
    gathers = re.findall(
        r'"stablehlo.gather"\(.*slice_sizes = array<i64: ([\d, ]+)>.*?'
        r': \(tensor<(\d+)', hlo)
    per_slot = [g for g in gathers
                if int(g[1]) >= nnz and int(g[0].split(",")[0]) == 1]
    assert not per_slot, per_slot


def test_csr_to_ell_zero_rows_regression():
    # n_rows == 0: indptr is the single sentinel 0 — conversion must
    # produce a well-formed all-padding ELL, and spmv must not launch a
    # zero-grid pallas call
    indptr = np.zeros(1, np.int32)
    empty_i = np.zeros(0, np.int32)
    empty_v = np.zeros(0, np.float32)
    ell = csr_to_ell(indptr, empty_i, empty_v, 0, 4)
    assert ell.values.shape == (0, 8)
    assert ell.indices.shape == (0, 8) and ell.valid.shape == (0, 8)
    x = np.ones(4, np.float32)
    y = spmv_ell(ell, x, interpret=True)
    assert y.shape == (0,)
    y2 = spmv_csr(indptr, empty_i, empty_v, x, n_rows=0, interpret=True)
    assert y2.shape == (0,)


def test_csr_to_ell_zero_rows_static_width_jittable():
    indptr = np.zeros(1, np.int32)
    empty_i = np.zeros(0, np.int32)
    empty_v = np.zeros(0, np.float32)
    ell = csr_to_ell(indptr, empty_i, empty_v, 0, 4, max_nnz_row=3)
    assert ell.values.shape == (0, 8)   # padded to pad_to
