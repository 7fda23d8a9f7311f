"""First-class sparse tensors: linalg.spmv_csr / linalg.spmm_csr through
the full trace → IR → PassManager → backend pipeline on every registered
backend, against a scipy CSR oracle (structured random + pathological
matrices), plus the sparsify pass's IR-level contract."""
import contextlib
import io

import jax
import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

from repro.core import backend as backend_mod
from repro.core import ops, pipeline
from repro.core.ir import SparseEncoding, TensorType
from repro.core.options import CompileOptions, use_options


def _csr(a):
    return (a.indptr.astype(np.int32), a.indices.astype(np.int32),
            a.data.astype(np.float32))


def _random_csr(rng, n, m, density):
    a = scipy_sparse.random(n, m, density=density, format="csr",
                            random_state=rng, dtype=np.float32)
    return a


def _empty_rows_csr():
    """Half the rows empty (the paper's StocF-like irregularity)."""
    dense = np.zeros((8, 6), np.float32)
    dense[1] = np.arange(1, 7)
    dense[4, 2] = 3.0
    dense[7, 5] = -2.0
    return scipy_sparse.csr_matrix(dense)


def _single_dense_row_csr():
    """One fully-dense row among sparse ones (max_nnz_row >> nnz_mean —
    stresses the ELL padding width)."""
    dense = np.zeros((16, 32), np.float32)
    dense[3] = np.linspace(-1, 1, 32)
    dense[0, 0] = 1.0
    dense[9, 31] = 5.0
    return scipy_sparse.csr_matrix(dense)


MATRICES = {
    "random": lambda rng: _random_csr(rng, 100, 80, 0.1),
    "empty-rows": lambda rng: _empty_rows_csr(),
    "dense-row": lambda rng: _single_dense_row_csr(),
}


def _all_targets():
    # every registered backend must compile the sparse ops end to end
    return backend_mod.available_backends()


@pytest.mark.parametrize("target", _all_targets())
@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_spmv_all_backends_vs_scipy(rng, target, matrix):
    a = MATRICES[matrix](rng)
    n, m = a.shape
    ip, ind, val = _csr(a)
    x = rng.standard_normal(m).astype(np.float32)
    with use_options(CompileOptions(target=target)):
        y = ops.spmv_csr(ip, ind, val, x, n_rows=n)
    np.testing.assert_allclose(np.asarray(y), a @ x, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("target", _all_targets())
@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_spmm_all_backends_vs_scipy(rng, target, matrix):
    a = MATRICES[matrix](rng)
    n, m = a.shape
    ip, ind, val = _csr(a)
    b = rng.standard_normal((m, 9)).astype(np.float32)
    with use_options(CompileOptions(target=target)):
        y = ops.spmm_csr(ip, ind, val, b, n_rows=n)
    np.testing.assert_allclose(np.asarray(y), a @ b, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("target", ["xla", "loops", "pallas"])
def test_nnz_zero_matrix(rng, target):
    """All-zero matrix (nnz == 0) must compile and produce zeros."""
    n, m = 7, 5
    ip = np.zeros(n + 1, np.int32)
    ind = np.zeros(0, np.int32)
    val = np.zeros(0, np.float32)
    x = rng.standard_normal(m).astype(np.float32)
    with use_options(CompileOptions(target=target)):
        y = ops.spmv_csr(ip, ind, val, x, n_rows=n)
    np.testing.assert_allclose(np.asarray(y), np.zeros(n), atol=0)


def test_emitted_source_nnz_zero_with_ell_convert(rng, tmp_path):
    """The freestanding source's _sparse_convert must survive an all-zero
    matrix (nnz == 0, n_rows > 0) when the backend inserts the ELL
    conversion (regression: val[idx] gathered out of a 0-length array)."""
    n, m = 7, 5
    ip = np.zeros(n + 1, np.int32)
    specs = [jax.ShapeDtypeStruct((n + 1,), np.int32),
             jax.ShapeDtypeStruct((0,), np.int32),
             jax.ShapeDtypeStruct((0,), np.float32),
             jax.ShapeDtypeStruct((m,), np.float32)]

    def f(ipv, indv, valv, xv):
        return ops.spmv_csr(ipv, indv, valv, xv, n_rows=n, max_nnz_row=0)

    mod = pipeline.compile(f, *specs, options=CompileOptions(
        target="loops", fuse_elementwise=False))
    assert "sparse.convert" in [o.opname for o in mod.graph.ops]
    g: dict = {}
    exec(compile(mod.emit_source(), "<gen>", "exec"), g)
    x = rng.standard_normal(m).astype(np.float32)
    y = g[mod.graph.name](ip, np.zeros(0, np.int32),
                          np.zeros(0, np.float32), x)
    np.testing.assert_allclose(np.asarray(y), np.zeros(n), atol=0)


def test_emitted_source_ell_windows_match_loops(rng):
    """The freestanding source's windowed _sparse_convert gives the same
    SpMV as the compiled loops backend on a matrix with empty rows whose
    last row is the longest (its window runs into the tail pad)."""
    lens = np.array([3, 0, 5, 0, 0, 2, 1, 0, 13])
    n, m = len(lens), 11
    ip = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    ind = rng.integers(0, m, ip[-1]).astype(np.int32)
    val = rng.standard_normal(ip[-1]).astype(np.float32)
    x = rng.standard_normal(m).astype(np.float32)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in (ip, ind, val, x)]

    def f(ipv, indv, valv, xv):
        return ops.spmv_csr(ipv, indv, valv, xv, n_rows=n,
                            max_nnz_row=int(lens.max()))

    mod = pipeline.compile(f, *specs, options=CompileOptions(
        target="loops", fuse_elementwise=False))
    assert "sparse.convert" in [o.opname for o in mod.graph.ops]
    g: dict = {}
    exec(compile(mod.emit_source(), "<gen>", "exec"), g)
    y = g[mod.graph.name](ip, ind, val, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(mod(ip, ind, val, x)),
                               rtol=1e-6, atol=1e-6)
    dense = np.zeros((n, m), np.float32)
    for r in range(n):
        np.add.at(dense[r], ind[ip[r]:ip[r + 1]], val[ip[r]:ip[r + 1]])
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-5,
                               atol=1e-5)


def test_no_registry_bypass_in_tracing():
    """spmv_csr inside a trace emits the composite sparse form — a
    sparse-encoded pack feeding linalg.spmv_csr, no loose-operand op."""
    from repro.core import tracer

    n, m = 12, 10
    specs = [jax.ShapeDtypeStruct((n + 1,), np.int32),
             jax.ShapeDtypeStruct((20,), np.int32),
             jax.ShapeDtypeStruct((20,), np.float32),
             jax.ShapeDtypeStruct((m,), np.float32)]

    def f(ip, ind, val, x):
        return ops.spmv_csr(ip, ind, val, x, n_rows=n)

    g = tracer.trace(f, *specs)
    names = [op.opname for op in g.ops]
    assert names == ["sparse.pack", "linalg.spmv_csr"]
    pack = g.ops[0]
    enc = pack.results[0].type.encoding
    assert enc is not None and enc.format == "csr" and enc.nnz == 20
    assert pack.results[0].type.shape == (n, m)
    spmv = g.ops[1]
    assert spmv.operands[0] is pack.results[0]   # composite value, not 3
    assert len(spmv.operands) == 2               # loose operands


def test_sparsify_appears_in_pipeline_dump(rng):
    """--print-ir-after-all shows the sparsify stage and its rewrites."""
    a = _random_csr(rng, 32, 24, 0.2)
    ip, ind, val = _csr(a)
    x = rng.standard_normal(24).astype(np.float32)
    specs = [jax.ShapeDtypeStruct(v.shape, v.dtype)
             for v in (ip, ind, val, x)]

    def f(ipv, indv, valv, xv):
        return ops.spmv_csr(ipv, indv, valv, xv, n_rows=32,
                            max_nnz_row=int(np.diff(ip).max()))

    buf = io.StringIO()
    opts = CompileOptions(target="pallas", print_ir_after_all=True)
    with contextlib.redirect_stdout(buf):
        mod = pipeline.compile(f, *specs, options=opts)
    dump = buf.getvalue()
    assert "IR after sparsify" in dump
    assert "kk.spmv" in dump
    assert "sparse.convert" in dump      # ELL layout change is IR-visible
    assert mod.graph.pipeline_stats["sparsify"] == 1


def test_ell_conversion_only_for_ell_backends(rng):
    """Library backends keep CSR; ell-layout backends get sparse.convert
    when the static width is known."""
    a = _random_csr(rng, 32, 24, 0.2)
    ip, ind, val = _csr(a)
    x = rng.standard_normal(24).astype(np.float32)
    specs = [jax.ShapeDtypeStruct(v.shape, v.dtype)
             for v in (ip, ind, val, x)]
    mx = int(np.diff(ip).max())

    def f(ipv, indv, valv, xv):
        return ops.spmv_csr(ipv, indv, valv, xv, n_rows=32, max_nnz_row=mx)

    mod_lib = pipeline.compile(f, *specs,
                               options=CompileOptions(target="xla"))
    assert "sparse.convert" not in [o.opname for o in mod_lib.graph.ops]
    mod_ell = pipeline.compile(f, *specs,
                               options=CompileOptions(target="loops"))
    convs = [o for o in mod_ell.graph.ops if o.opname == "sparse.convert"]
    assert len(convs) == 1
    assert convs[0].results[0].type.encoding.format == "ell"


def test_sparse_encoding_type_printing():
    enc = SparseEncoding(format="csr", nnz=100, nnz_mean=12.5,
                         max_nnz_row=40)
    t = TensorType((10, 10), "float32", encoding=enc)
    assert t.is_sparse
    s = str(t)
    assert "#sparse<csr" in s and "nnz=100" in s and "max/row=40" in s


def test_sparse_nbytes_counts_stored_entries():
    enc = SparseEncoding(format="csr", nnz=100)
    t = TensorType((1000, 1000), "float32", encoding=enc)
    dense = TensorType((1000, 1000), "float32")
    # 100 * (4 value bytes + 4 crd bytes) + 1001 * 4 pos bytes
    assert t.nbytes == 100 * 8 + 1001 * 4
    assert t.nbytes < dense.nbytes
    # padded ELL is rectangular: rows × (8-padded max/row) planes of
    # values + indices + valid, no pos array
    ell = TensorType((16, 32), "float32",
                     encoding=SparseEncoding(format="ell", nnz=35,
                                             max_nnz_row=32))
    assert ell.nbytes == 16 * 32 * (4 + 4 + 1)
