"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode, which every other kernel test runs, cannot see what the
chip's compiler refuses: block shapes off the (8, 128) tiling, more VMEM
than a kernel may take, a program that does not fit.  These tests compile
for a *described* ``v5e:2x2`` topology (no chip needed) at qwen2-1.5b's
published widths and at StocF-1465's full row count, and check that each
kernel became a Mosaic custom call.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every pytest worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo import kernel_calls

# qwen2-1.5b published widths
LAYERS, D_MODEL, HQ, HKV, HEAD_DIM = 28, 1536, 12, 2, 128
BLOCK, SLOTS, MAX_BLOCKS = 16, 8, 36
STOCF_ROWS, STOCF_ELL_WIDTH = 1_465_137, 192      # max_nnz_row 189 → 192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernels(fn, *args):
    return kernel_calls(jax.jit(fn).lower(*args).compile().as_text())


def test_decode_attention(one_chip):
    from repro.kernels.decode_attention import decode_attention
    s = lambda *a: _spec(one_chip, *a)  # noqa: E731
    got = _kernels(decode_attention,
                   s((SLOTS, HQ, HEAD_DIM), "bfloat16"),
                   s((SLOTS, HKV, 2048, HEAD_DIM), "bfloat16"),
                   s((SLOTS, HKV, 2048, HEAD_DIM), "bfloat16"),
                   s((SLOTS,), "int32"))
    assert got == {"decode_attention": 1}


def test_flash_attention_prefill(one_chip):
    from repro.kernels.flash_attention import flash_attention
    s = lambda *a: _spec(one_chip, *a)  # noqa: E731
    got = _kernels(flash_attention,
                   s((1, HQ, 512, HEAD_DIM), "bfloat16"),
                   s((1, HKV, 512, HEAD_DIM), "bfloat16"),
                   s((1, HKV, 512, HEAD_DIM), "bfloat16"))
    assert got == {"flash_attention": 1}


def test_rmsnorm(one_chip):
    from repro.kernels.rmsnorm import rmsnorm
    got = _kernels(rmsnorm, _spec(one_chip, (512, D_MODEL), "bfloat16"),
                   _spec(one_chip, (D_MODEL,), "float32"))
    assert got == {"rmsnorm": 1}


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_page_gather(one_chip, dtype):
    from repro.kernels.paged_kv import page_gather_pallas
    got = _kernels(
        lambda pool, table, lengths: page_gather_pallas(
            pool, table, lengths, block_size=BLOCK),
        _spec(one_chip, (1 + MAX_BLOCKS * (SLOTS + 1), HKV, BLOCK,
                         HEAD_DIM), dtype),
        _spec(one_chip, (SLOTS, MAX_BLOCKS), "int32"),
        _spec(one_chip, (SLOTS,), "int32"))
    assert got == {"page_gather": 1}


def test_spmv_ell_stocf_rows(one_chip):
    from repro.kernels.spmv import EllMatrix, spmv_ell
    s = lambda *a: _spec(one_chip, *a)  # noqa: E731
    n, w = STOCF_ROWS, STOCF_ELL_WIDTH
    got = _kernels(
        lambda v, i, m, x: spmv_ell(EllMatrix(v, i, m, n, n, 14.34), x),
        s((n, w), "float32"), s((n, w), "int32"), s((n, w), "bool"),
        s((n,), "float32"))
    assert got == {"spmv_ell": 1}


def test_csr_to_ell_stocf_rows(one_chip):
    """The CSR to ELL conversion at StocF-1465's size is the one
    ``csr_to_ell`` window kernel, with no scalar gather beside it."""
    from repro.kernels.spmv import csr_to_ell
    s = lambda *a: _spec(one_chip, *a)  # noqa: E731
    n, nnz = STOCF_ROWS, 21_009_064                 # nnz mean 14.34
    hlo = jax.jit(lambda ip, ind, val: csr_to_ell(
        ip, ind, val, n, n, max_nnz_row=189, interpret=False)).lower(
        s((n + 1,), "int32"), s((nnz,), "int32"),
        s((nnz,), "float32")).compile().as_text()
    assert kernel_calls(hlo) == {"csr_to_ell": 1}
    assert "gather" not in hlo


def test_paged_decode_step(one_chip):
    """The whole 28-layer paged decode step on the pallas target: every
    attention-path kernel is a custom call and the step fits one chip."""
    from repro.configs import get_config
    from repro.core.options import CompileOptions, use_options
    from repro.models.model import build_model
    cfg = get_config("qwen2-1.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (LAYERS, D_MODEL, HQ, HKV, HEAD_DIM)
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape,
                        "bfloat16" if jnp.issubdtype(a.dtype, jnp.floating)
                        else a.dtype), model.abstract())
    pools = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_paged_cache(
            1 + MAX_BLOCKS * (SLOTS + 1), BLOCK)))
    i32 = lambda *shape: _spec(one_chip, shape, "int32")  # noqa: E731
    with use_options(CompileOptions(target="pallas", interpret=False)):
        compiled = jax.jit(
            lambda p, t, c, tb, ln: model.paged_decode_step(
                p, t, c, tb, ln, block_size=BLOCK)).lower(
            params, i32(SLOTS), pools, i32(SLOTS, MAX_BLOCKS),
            i32(SLOTS)).compile()
    got = kernel_calls(compiled.as_text())
    # one scanned layer body: ln1 + ln2 + final norm, k and v gathers
    assert got == {"rmsnorm": 3, "page_gather": 2, "decode_attention": 1}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
