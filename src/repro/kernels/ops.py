"""Kernel wrappers + registry registrations (the Kokkos Kernels surface).

Each ``kk.*`` op gets two implementations:

* ``xla``    — the pure-jnp oracle from ``ref.py`` (the "vendor library"
               path: XLA's MXU lowering is TPU's cuBLAS);
* ``pallas`` — the hand-tiled kernel, differentiable via ``custom_vjp``
               whose backward is derived from the oracle (kernelized
               backward = future work, noted in DESIGN.md).

Model code calls the top-level wrappers (``attention``, ``rwkv6`` …), which
consult ``CompileOptions`` — the LAPIS pipeline's library-vs-generated-code
decision applied at runtime.  ``target="auto"`` resolves to kernels on TPU
and the library path on CPU hosts (tests force ``pallas`` + interpret).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.options import CompileOptions, current_options
from repro.core.registry import register
from repro.kernels import ref
from repro.kernels import batched_gemm as _bg
from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import paged_kv as _pk  # noqa: F401  (registers kokkos.page_*)
from repro.kernels import rglru as _rg
from repro.kernels import rmsnorm as _rn
from repro.kernels import rwkv6 as _rw
from repro.kernels import spmv as _sp


# ---------------------------------------------------------------------------
# custom_vjp plumbing: kernel forward, oracle backward
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kernelized(kernel_fn, ref_fn, static_kv: tuple):
    static = dict(static_kv)

    @jax.custom_vjp
    def f(*args):
        return kernel_fn(*args, **static)

    def fwd(*args):
        return kernel_fn(*args, **static), args

    def bwd(saved, g):
        ref_static = {k: v for k, v in static.items()
                      if k not in ("interpret", "tiling", "bq", "bkv",
                                   "chunk", "d_block", "bm", "bn", "bk",
                                   "batch_block", "vectorize_batch",
                                   "block_rows", "row_block", "row_width",
                                   "max_nnz_row")}
        _, vjp = jax.vjp(lambda *a: ref_fn(*a, **ref_static), *saved)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


# ---------------------------------------------------------------------------
# kk.gemm
# ---------------------------------------------------------------------------

@register("kk.gemm", "xla")
def gemm_xla(a, b, *, tiling=None):
    return ref.matmul(a, b)


@register("kk.gemm", "pallas")
def gemm_pallas(a, b, *, tiling=None, interpret=False):
    t = tiling or {}
    kw = {"bm": t.get("bm", 128), "bn": t.get("bn", 128),
          "bk": t.get("bk", 512), "interpret": interpret}
    f = _kernelized(_mm.matmul, ref.matmul, _freeze(kw))
    return f(a, b)


# ---------------------------------------------------------------------------
# kk.gemv — on TPU a gemv is a degenerate gemm; route through the MXU path
# ---------------------------------------------------------------------------

@register("kk.gemv", "xla")
def gemv_xla(a, x, *, tiling=None):
    return ref.gemv(a, x)


@register("kk.gemv", "pallas")
def gemv_pallas(a, x, *, tiling=None, interpret=False):
    t = tiling or {}
    kw = {"bm": t.get("bm", 256), "bn": 128, "bk": t.get("bk", 512),
          "interpret": interpret}
    f = _kernelized(_mm.matmul, ref.matmul, _freeze(kw))
    return f(a, x[:, None])[:, 0]


# ---------------------------------------------------------------------------
# kk.batched_gemm
# ---------------------------------------------------------------------------

@register("kk.batched_gemm", "xla")
def batched_gemm_xla(a, b, *, tiling=None):
    return ref.batched_gemm(a, b)


@register("kk.batched_gemm", "pallas")
def batched_gemm_pallas(a, b, *, tiling=None, interpret=False):
    t = tiling or {}
    kw = {"batch_block": t.get("batch_block", 8),
          "vectorize_batch": t.get("vectorize_batch"),
          "bm": t.get("bm", 128), "bn": t.get("bn", 128),
          "bk": t.get("bk", 512), "interpret": interpret}
    f = _kernelized(_bg.batched_gemm, ref.batched_gemm, _freeze(kw))
    return f(a, b)


# ---------------------------------------------------------------------------
# kk.spmv / kk.spmm — operands arrive as the composite sparse value a
# sparse.pack / sparse.convert op produced (CsrMatrix or EllMatrix)
# ---------------------------------------------------------------------------

@register("kk.spmv", "xla")
def spmv_xla(a, x, *, tiling=None, max_nnz_row=None):
    return _sp.spmv_reference(a, x)


@register("kk.spmv", "pallas")
def spmv_pallas(a, x, *, tiling=None, max_nnz_row=None, interpret=False):
    if isinstance(a, _sp.CsrMatrix) and max_nnz_row is None:
        # no static ELL width (matrix stats unknown at compile time):
        # the layout conversion is not jit-safe — run library semantics
        return _sp.spmv_reference(a, x)
    t = tiling or {}
    ell = _sp.as_ell(a, max_nnz_row=max_nnz_row, interpret=interpret)
    return _sp.spmv_ell(ell, x, row_block=t.get("row_block", 256),
                        row_width=t.get("row_width", 128),
                        interpret=interpret)


@register("kk.spmm", "xla")
def spmm_xla(a, b, *, tiling=None, max_nnz_row=None):
    return _sp.spmm_reference(a, b)


@register("kk.spmm", "pallas")
def spmm_pallas(a, b, *, tiling=None, max_nnz_row=None, interpret=False):
    from repro.kernels import spmm as _spmm
    if isinstance(a, _sp.CsrMatrix) and max_nnz_row is None:
        return _sp.spmm_reference(a, b)
    t = tiling or {}
    return _spmm.spmm_sparse(a, b, row_block=t.get("row_block", 128),
                             row_width=t.get("row_width", 128),
                             max_nnz_row=max_nnz_row, interpret=interpret)


# ---------------------------------------------------------------------------
# model-facing wrappers (options-driven dispatch)
# ---------------------------------------------------------------------------

def _use_pallas(options: Optional[CompileOptions]) -> bool:
    """Backend-policy query: hand-written kernels or the jnp oracle?
    (``pallas`` → always kernels; ``auto`` → kernels iff a real TPU backs
    them; library/reference backends → oracle.)"""
    options = options or current_options()
    return options.backend().wants_kernels(options)


CHUNKED_ATTN_THRESHOLD = 2048     # longest S computed as one dense block


@functools.lru_cache(maxsize=None)
def _flash_ckpt(causal, window, scale, logit_softcap):
    from repro.kernels.chunked import flash_chunked_attention

    def call(q, k, v):
        return flash_chunked_attention(q, k, v, causal=causal,
                                       window=window, scale=scale,
                                       logit_softcap=logit_softcap)

    return jax.checkpoint(
        call, policy=jax.checkpoint_policies.nothing_saveable)


def attention(q, k, v, *, causal=True, window=None, scale=None,
              logit_softcap=None,
              options: Optional[CompileOptions] = None):
    """GQA attention — flash kernel on TPU / pallas target; on the library
    path short sequences use one dense softmax block, long sequences the
    chunked online-softmax form (O(chunk²) live memory — required for the
    4k/32k assigned cells, where a dense (B,H,S,S) tensor would dwarf
    HBM)."""
    options = options or current_options()
    if _use_pallas(options):
        kw = {"causal": causal, "window": window, "scale": scale,
              "logit_softcap": logit_softcap,
              "interpret": options.resolve_interpret()}
        f = _kernelized(_fa.flash_attention, ref.attention,
                        _freeze(kw))
        return f(q, k, v)
    if max(q.shape[2], k.shape[2]) > CHUNKED_ATTN_THRESHOLD:
        # §Perf iterations 1+3: flash custom-vjp chunked attention (bwd
        # recomputes probabilities; fwd saves only q,k,v,out,lse), nested
        # under its own checkpoint so the scan linearization cannot stack
        # cond-branch residuals per chunk pair (3.6× byte reduction at
        # equal flops — see EXPERIMENTS.md §Perf)
        return _flash_ckpt(causal, window, scale, logit_softcap)(q, k, v)
    return ref.attention(q, k, v, causal=causal, window=window, scale=scale,
                         logit_softcap=logit_softcap)


def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     scale=None,
                     options: Optional[CompileOptions] = None):
    """One-token cached attention.  Pallas cache-streaming kernel on TPU /
    pallas target (kernels/decode_attention.py); XLA oracle on CPU and in
    the dry-run (decode is HBM-bound either way — the kernel buys the
    fused online-softmax sweep with VMEM-resident state)."""
    options = options or current_options()
    if _use_pallas(options):
        from repro.kernels import decode_attention as _da
        kw = {"window": window, "scale": scale,
              "interpret": options.resolve_interpret()}
        f = _kernelized(_da.decode_attention, ref.decode_attention,
                        _freeze(kw))
        return f(q, k_cache, v_cache, lengths)
    return ref.decode_attention(q, k_cache, v_cache, lengths,
                                window=window, scale=scale)


def _rwkv6_ref_y(r, k, v, w, u):
    return ref.rwkv6_scan(r, k, v, w, u)[0]


def _rglru_ref_y(x, r, i, la):
    return ref.rglru_scan(x, r, i, la)[0]


def rwkv6(r, k, v, w, u, *, options: Optional[CompileOptions] = None):
    options = options or current_options()
    if _use_pallas(options):
        kw = {"chunk": 128, "interpret": options.resolve_interpret()}
        f = _kernelized(_rw.rwkv6_scan, _rwkv6_ref_y, _freeze(kw))
        return f(r, k, v, w, u)
    return _rwkv6_ref_y(r, k, v, w, u)


def rglru(x, r_gate, i_gate, log_a_param, *,
          options: Optional[CompileOptions] = None):
    options = options or current_options()
    if _use_pallas(options):
        kw = {"chunk": 128, "d_block": 512,
              "interpret": options.resolve_interpret()}
        f = _kernelized(_rg.rglru_scan, _rglru_ref_y, _freeze(kw))
        return f(x, r_gate, i_gate, log_a_param)
    return _rglru_ref_y(x, r_gate, i_gate, log_a_param)


def rmsnorm(x, weight, *, eps=1e-6,
            options: Optional[CompileOptions] = None):
    options = options or current_options()
    if _use_pallas(options):
        kw = {"eps": eps, "interpret": options.resolve_interpret()}
        f = _kernelized(_rn.rmsnorm, ref.rmsnorm, _freeze(kw))
        return f(x, weight)
    return ref.rmsnorm(x, weight, eps=eps)


# registry entries for the model-facing ops too (pipeline completeness)
register("kk.attention", "xla")(
    lambda q, k, v, *, tiling=None, **kw: ref.attention(q, k, v, **kw))
register("kk.attention", "pallas")(
    lambda q, k, v, *, tiling=None, interpret=False, **kw:
    _fa.flash_attention(q, k, v, interpret=interpret, **kw))
register("kk.rwkv6_scan", "xla")(
    lambda r, k, v, w, u, *, tiling=None: ref.rwkv6_scan(r, k, v, w, u)[0])
register("kk.rwkv6_scan", "pallas")(
    lambda r, k, v, w, u, *, tiling=None, interpret=False:
    _rw.rwkv6_scan(r, k, v, w, u, interpret=interpret))
register("kk.rglru_scan", "xla")(
    lambda x, r, i, la, *, tiling=None: ref.rglru_scan(x, r, i, la)[0])
register("kk.rglru_scan", "pallas")(
    lambda x, r, i, la, *, tiling=None, interpret=False:
    _rg.rglru_scan(x, r, i, la, interpret=interpret))
register("kk.conv2d", "xla")(
    lambda x, w, *, stride=(1, 1), padding="SAME", tiling=None:
    jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(stride), padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW")))
