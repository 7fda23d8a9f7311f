"""The Kokkos emitter, adapted (paper §4.4).

Two outputs from a lowered graph:

* ``build_callable`` — an executable JAX callable (the KokkosBackend /
  RefBackend-replacement path of the paper's §5 pipeline).  ``kk.*`` ops
  dispatch through the registry (library vs Pallas); mapped
  ``kokkos.range_parallel`` / ``kokkos.team_parallel`` nests become
  ``pl.pallas_call`` invocations built from the map_parallelism attrs
  (collapsed nests on library backends run as one fused call, and a
  backend's op-executor hook may claim them outright); ``kokkos.sync``
  drives the lazy DualView runtime.

* ``emit_python_source`` — freestanding Python source with **weights
  embedded** (the paper's "C++ file with no dependencies besides Kokkos,
  all model weights included as constant arrays"; ours needs only
  jax+numpy).  Ships as a single .py: constants ride along as a
  base64-encoded npz blob.

Like the paper's emitter we walk the SSA graph in order, bind each result
to a fresh variable, and inline scalar constants as literals.
"""
from __future__ import annotations

import base64
import io
import textwrap
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import refs
from repro.core.dualview import DualView
from repro.core.ir import Graph, MemorySpace, Op
from repro.core.irwalk import ValueNamer, bind_region_args, constant_label
from repro.core.options import CompileOptions, current_options


# ---------------------------------------------------------------------------
# executable path
# ---------------------------------------------------------------------------

def _parallel_callable(op: Op, options: CompileOptions) -> Callable:
    """Materialize a mapped kokkos.*_parallel nest as a Pallas call
    (map/reduce kernels are generic; the body from the IR runs on blocks
    shaped by the backend's hierarchy).  A nest carrying a fused region
    executes the whole multi-op body inside ONE kernel — intermediates
    never leave scratch (``generic.block_map_region``)."""
    from repro.kernels import generic
    kind = op.attrs["kind"]
    tiling = op.attrs["tiling"]
    interpret = options.resolve_interpret()
    out_shape = op.results[0].type.shape
    out_dtype = op.results[0].type.dtype
    if op.regions:
        region = op.regions[0]
        return lambda *a: generic.block_map_region(
            region, a, out_shape, out_dtype,
            block=tiling["block"], interpret=interpret)
    fn = op.attrs["fn"]
    if kind in ("map", "reduce"):  # softmax/axis-reduce also runs on blocks
        return lambda *a: generic.block_map(
            fn, a, out_shape, out_dtype,
            block=tiling["block"], interpret=interpret)
    raise NotImplementedError(kind)


def _op_callable(op: Op, options: CompileOptions) -> Optional[Callable]:
    from repro.core import registry
    # a backend may claim any op outright (e.g. the `loops` reference
    # backend interprets kokkos.*_parallel nests in pure jnp, no Pallas)
    backend = options.backend()
    if backend.op_executor is not None:
        ex = backend.op_executor(op, options)
        if ex is not None:
            return ex
    if op.opname == "sparse.pack":
        # assemble the composite sparse value the encoding describes
        from repro.kernels.spmv import CsrMatrix
        n_rows, n_cols = op.results[0].type.shape
        return lambda ip, ind, val: CsrMatrix(ip, ind, val, n_rows, n_cols)
    if op.opname == "sparse.convert":
        from repro.kernels.spmv import as_ell
        mx = op.attrs.get("max_nnz_row")
        interpret = options.resolve_interpret()
        return lambda a, _mx=mx: as_ell(a, max_nnz_row=_mx,
                                        interpret=interpret)
    if op.opname == "kokkos.fused":
        # an unlowered fused region (e.g. mixed operand shapes kept it at
        # tensor level): interpret the structured body; XLA fuses the jnp
        return refs.region_ref(op.regions[0])
    if op.opname.startswith("kk."):
        tiling = op.attrs.get("tiling")
        fn = registry.dispatch(op.opname, options)
        if tiling:
            return lambda *a, _fn=fn, _t=tiling: _fn(*a, tiling=_t,
                                                     **_op_kwargs(op))
        return lambda *a, _fn=fn: _fn(*a, **_op_kwargs(op))
    if op.opname in ("kokkos.page_gather", "kokkos.page_append",
                     "kokkos.page_copy"):
        # paged-KV cache plumbing dispatches through the registry like
        # kk.* library calls; the nest/tiling attrs describe the mapped
        # loop structure the backend implementation realizes
        fn = registry.dispatch(op.opname, options)
        bs = int(op.attrs["block_size"])
        return lambda *a, _fn=fn, _bs=bs: _fn(*a, block_size=_bs)
    if op.opname in ("kokkos.range_parallel", "kokkos.team_parallel"):
        if op.attrs.get("collapse"):
            # library mapping: the whole nest is one fused kk.*-style
            # call — the composed jnp body, fused by the library's jit
            return op.attrs["fn"]
        return _parallel_callable(op, options)
    return None


def _op_kwargs(op: Op) -> dict:
    """Forward data-independent attrs that implementations accept."""
    out = {}
    if op.opname in ("kk.spmv", "kk.spmm"):
        out["max_nnz_row"] = op.attrs.get("max_nnz_row")
    if op.opname == "kk.conv2d":
        out["stride"] = tuple(op.attrs["stride"])
        out["padding"] = op.attrs["padding"]
    return out


def build_callable(graph: Graph,
                   options: Optional[CompileOptions] = None,
                   jit: bool = True) -> Callable:
    """Walk the lowered graph once, binding each op to an executor; return
    ``fn(*inputs) -> outputs`` (jit-wrapped by default)."""
    options = options or current_options()

    # constants → DualViews (host-resident until first device use; the
    # kokkos.sync inserted by memory_space_management triggers the lazy
    # h2d copy)
    const_views: dict = {}
    executors = []  # (op, callable|None)
    for op in graph.ops:
        if op.opname == "tensor.constant":
            dv = DualView.from_host(op.attrs["value"],
                                    name=f"const_{op.results[0].id}")
            const_views[op.results[0].id] = dv
            executors.append((op, None))
        elif op.opname == "kokkos.sync":
            executors.append((op, None))
        elif op.opname == "kokkos.modify":
            executors.append((op, None))
        else:
            ex = _op_callable(op, options)
            if ex is None:
                ex = refs.op_ref(op.opname, op.attrs)
            executors.append((op, ex))

    input_ids = [v.id for v in graph.inputs]
    output_ids = [v.id for v in graph.outputs]

    def run(*args):
        if len(args) != len(input_ids):
            raise TypeError(f"{graph.name} expects {len(input_ids)} args, "
                            f"got {len(args)}")
        env = dict(zip(input_ids, args))
        for op, ex in executors:
            if op.opname == "tensor.constant":
                dv = const_views[op.results[0].id]
                # value lands in env at sync time (lazy); put view for now
                env[op.results[0].id] = dv
            elif op.opname == "kokkos.sync":
                v = env[op.operands[0].id]
                if op.attrs.get("space") == "host_roundtrip":
                    # eager baseline-MLIR mode: force d2h + h2d around
                    # every kernel (measured by the resnet bench ablation;
                    # requires the unjitted executable — tracers skip)
                    if not isinstance(v, jax.core.Tracer) and \
                            not isinstance(v, DualView):
                        from repro.core.dualview import TRANSFERS
                        host = np.asarray(v)
                        TRANSFERS["d2h"] += 1
                        env[op.operands[0].id] = jax.device_put(host)
                        TRANSFERS["h2d"] += 1
                elif isinstance(v, DualView):
                    env[op.operands[0].id] = v.device()  # lazy h2d
            elif op.opname == "kokkos.modify":
                v = env[op.operands[0].id]
                if isinstance(v, DualView):
                    v.modify_device()
            else:
                vals = []
                for o in op.operands:
                    x = env[o.id]
                    vals.append(x.device() if isinstance(x, DualView) else x)
                out = ex(*vals)
                if len(op.results) == 1:
                    env[op.results[0].id] = out
                else:
                    for r, v in zip(op.results, out):
                        env[r.id] = v
        outs = []
        for oid in output_ids:
            v = env[oid]
            outs.append(v.device() if isinstance(v, DualView) else v)
        return outs[0] if len(outs) == 1 else tuple(outs)

    # kernel-launch count: one dispatch per bound executor (constants and
    # sync/modify bookkeeping are not launches).  A fused chain of N
    # elementwise ops contributes ONE — the launch-count bench and the
    # fusion acceptance test read this.
    launch_count = sum(1 for _, ex in executors if ex is not None)

    run.const_views = const_views
    run.graph = graph
    run.launch_count = launch_count
    if jit:
        jitted = jax.jit(run)

        def wrapper(*args):
            return jitted(*args)
        wrapper.const_views = const_views
        wrapper.graph = graph
        wrapper.unjitted = run
        wrapper.launch_count = launch_count
        return wrapper
    return run


# ---------------------------------------------------------------------------
# source path (freestanding .py with embedded weights)
# ---------------------------------------------------------------------------

_SRC_OPS = {
    "linalg.add": "jnp.add({0}, {1})",
    "linalg.sub": "jnp.subtract({0}, {1})",
    "linalg.mul": "jnp.multiply({0}, {1})",
    "linalg.div": "jnp.divide({0}, {1})",
    "linalg.maximum": "jnp.maximum({0}, {1})",
    "linalg.relu": "jax.nn.relu({0})",
    "linalg.gelu": "jax.nn.gelu({0}, approximate=True)",
    "linalg.silu": "jax.nn.silu({0})",
    "linalg.sigmoid": "jax.nn.sigmoid({0})",
    "linalg.tanh": "jnp.tanh({0})",
    "linalg.exp": "jnp.exp({0})",
    "linalg.neg": "jnp.negative({0})",
    "linalg.sqrt": "jnp.sqrt({0})",
    "linalg.rsqrt": "jax.lax.rsqrt({0})",
    "linalg.matmul": "jnp.matmul({0}, {1})",
    "linalg.batch_matmul": "jnp.matmul({0}, {1})",
    "linalg.gemv": "jnp.matmul({0}, {1})",
    "linalg.dot": "jnp.dot({0}, {1})",
    "kk.gemm": "jnp.matmul({0}, {1})",
    "kk.batched_gemm": "jnp.matmul({0}, {1})",
    "kk.gemv": "jnp.matmul({0}, {1})",
    "linalg.avg_pool_global": "jnp.mean({0}, axis=(2, 3))",
}


def _src_line(op: Op, names: dict) -> str:
    a = [names[o.id] for o in op.operands]
    res = names[op.results[0].id]
    tmpl = _SRC_OPS.get(op.opname)
    if tmpl is not None:
        return f"{res} = {tmpl.format(*a)}"
    at = op.attrs
    if op.opname == "linalg.power":
        return f"{res} = jnp.power({a[0]}, {at['exponent']!r})"
    if op.opname == "linalg.reduce_sum":
        return (f"{res} = jnp.sum({a[0]}, axis={at.get('axis')!r}, "
                f"keepdims={at.get('keepdims', False)!r})")
    if op.opname == "linalg.reduce_max":
        return (f"{res} = jnp.max({a[0]}, axis={at.get('axis')!r}, "
                f"keepdims={at.get('keepdims', False)!r})")
    if op.opname == "linalg.mean":
        return (f"{res} = jnp.mean({a[0]}, axis={at.get('axis')!r}, "
                f"keepdims={at.get('keepdims', False)!r})")
    if op.opname == "linalg.softmax":
        return f"{res} = jax.nn.softmax({a[0]}, axis={at.get('axis', -1)!r})"
    if op.opname == "tensor.reshape":
        return f"{res} = jnp.reshape({a[0]}, {at['shape']!r})"
    if op.opname == "tensor.transpose":
        return f"{res} = jnp.transpose({a[0]}, {at.get('perm')!r})"
    if op.opname == "tensor.cast":
        return f"{res} = {a[0]}.astype({at['dtype']!r})"
    if op.opname == "tensor.slice":
        return (f"{res} = jax.lax.dynamic_slice({a[0]}, {at['starts']!r}, "
                f"{at['sizes']!r})")
    if op.opname == "tensor.concat":
        return (f"{res} = jnp.concatenate(({', '.join(a)},), "
                f"axis={at.get('axis', 0)!r})")
    if op.opname == "tensor.broadcast":
        return f"{res} = jnp.broadcast_to({a[0]}, {at['shape']!r})"
    if op.opname == "tensor.pad":
        return (f"{res} = jnp.pad({a[0]}, {at['pads']!r}, "
                f"constant_values={at.get('value', 0.0)!r})")
    if op.opname == "tensor.gather":
        return f"{res} = jnp.take({a[0]}, {a[1]}, axis={at.get('axis', 0)!r})"
    if op.opname == "sparse.pack":
        n_rows, n_cols = op.results[0].type.shape
        return (f"{res} = _sparse_pack({a[0]}, {a[1]}, {a[2]}, "
                f"{n_rows}, {n_cols})")
    if op.opname == "sparse.convert":
        return (f"{res} = _sparse_convert({a[0]}, "
                f"{at.get('max_nnz_row')!r})")
    if op.opname in ("linalg.spmv_csr", "kk.spmv"):
        return f"{res} = _spmv({a[0]}, {a[1]})"
    if op.opname in ("linalg.spmm_csr", "kk.spmm"):
        return f"{res} = _spmm({a[0]}, {a[1]})"
    if op.opname == "kk.conv2d":
        return (f"{res} = jax.lax.conv_general_dilated({a[0]}, {a[1]}, "
                f"window_strides={tuple(at['stride'])!r}, "
                f"padding={at['padding']!r}, "
                f"dimension_numbers=('NCHW', 'OIHW', 'NCHW'))")
    if op.opname == "linalg.batch_norm":
        return (f"{res} = _batch_norm({', '.join(a)}, "
                f"eps={at.get('eps', 1e-5)!r})")
    if op.opname in ("paged.gather", "kokkos.page_gather"):
        return (f"{res} = _page_gather({a[0]}, {a[1]}, {a[2]}, "
                f"{at['block_size']!r})")
    if op.opname in ("paged.append", "kokkos.page_append"):
        return (f"{res} = _page_append({a[0]}, {a[1]}, {a[2]}, {a[3]}, "
                f"{at['block_size']!r})")
    if op.opname in ("paged.copy", "paged.swap_in", "paged.swap_out",
                     "kokkos.page_copy"):
        return (f"{res} = _page_copy({a[0]}, {a[1]}, {a[2]}, {a[3]}, "
                f"{at['block_size']!r})")
    if op.opname == "linalg.max_pool2d":
        return (f"{res} = jax.lax.reduce_window({a[0]}, -jnp.inf, "
                f"jax.lax.max, {(1, 1) + tuple(at['window'])!r}, "
                f"{(1, 1) + tuple(at['stride'])!r}, {at['padding']!r})")
    raise NotImplementedError(f"source emission for {op.opname}")


def _fused_region_lines(op: Op, names: ValueNamer) -> list:
    """Serialize a ``kokkos.fused`` region (or a parallel nest lowered
    from one) by re-emitting its recorded sub-op chain: block args bind
    to the outer operands' names (:func:`~repro.core.irwalk.
    bind_region_args` — the same routing the C++ path replays), each
    sub-op becomes an ordinary source line, and the op's result takes
    the yielded value's name.  The body is IR data, so the source path
    is total on fused graphs."""
    region = op.regions[0]
    local = bind_region_args(op, names)
    lines = ["# kokkos.fused: " +
             " -> ".join(s.opname for s in region.ops)]
    for sub in region.ops:
        for r in sub.results:
            local[r.id] = names.fresh()
        lines.append(_src_line(sub, local))
    for r, out in zip(op.results, region.outputs):
        names[r.id] = local[out.id]
    return lines


_PRELUDE = '''\
"""Auto-generated by repro (LAPIS-style emitter). Freestanding: depends only
on jax + numpy. Model weights are embedded below as a base64 npz blob (the
paper embeds them as C++ constant arrays)."""
import base64
import io

import jax
import jax.numpy as jnp
import numpy as np


def _sparse_pack(indptr, indices, values, n_rows, n_cols):
    """Composite CSR value (tagged tuple — freestanding analogue of the
    compiler's CsrMatrix)."""
    return ("csr", indptr, indices, values, n_rows, n_cols)


def _sparse_convert(a, max_nnz_row):
    """CSR -> padded-ELL layout change (sparse.convert).  The width is
    an inlined copy of repro.core.ir.ell_storage_width (this module is
    freestanding and cannot import it)."""
    _, ip, ind, val, n_rows, n_cols = a
    width = max(-(-max(max_nnz_row, 1) // 8) * 8, 8)
    if n_rows == 0 or val.shape[0] == 0:
        # degenerate matrix: all-padding ELL (gathering val[idx] from a
        # zero-length values array would be out of bounds)
        return ("ell", jnp.zeros((n_rows, width), val.dtype),
                jnp.zeros((n_rows, width), jnp.int32),
                jnp.zeros((n_rows, width), bool), n_rows, n_cols)
    row_len = ip[1:] - ip[:-1]
    valid = jnp.arange(width)[None, :] < row_len[:, None]

    def windows(arr):
        # row r is the contiguous window arr[ip[r] : ip[r] + width]; the
        # tail pad keeps dynamic_slice from shifting the last rows'
        # windows back to fit
        padded = jnp.pad(arr, (0, width))
        return jax.vmap(lambda s: jax.lax.dynamic_slice(
            padded, (s,), (width,)))(ip[:-1])

    vals = jnp.where(valid, windows(val), 0).astype(val.dtype)
    cols = jnp.where(valid, windows(ind), 0).astype(jnp.int32)
    return ("ell", vals, cols, valid, n_rows, n_cols)


def _spmv(a, x):
    if a[0] == "ell":
        _, vals, cols, valid, n_rows, _ = a
        return jnp.sum(vals * jnp.where(valid, x[cols], 0.0),
                       axis=1).astype(x.dtype)
    _, ip, ind, val, n_rows, _ = a
    if val.shape[0] == 0:
        return jnp.zeros((n_rows,), x.dtype)
    row_ids = jnp.cumsum(
        jnp.zeros(val.shape[0], jnp.int32).at[ip[1:-1]].add(1))
    return jax.ops.segment_sum(val * x[ind], row_ids,
                               num_segments=n_rows)


def _spmm(a, b):
    if a[0] == "ell":
        _, vals, cols, valid, n_rows, _ = a
        b_g = jnp.where(valid[:, :, None], b[cols], 0.0)
        return jnp.sum(vals[:, :, None] * b_g, axis=1).astype(b.dtype)
    _, ip, ind, val, n_rows, _ = a
    if val.shape[0] == 0:
        return jnp.zeros((n_rows, b.shape[1]), b.dtype)
    row_ids = jnp.cumsum(
        jnp.zeros(val.shape[0], jnp.int32).at[ip[1:-1]].add(1))
    return jax.ops.segment_sum(val[:, None] * b[ind], row_ids,
                               num_segments=n_rows)


def _page_gather(pool, table, lengths, block_size):
    """Assemble each slot's contiguous KV view from its page-table blocks
    (kokkos.page_gather; stale positions past `lengths` are masked by the
    consuming attention kernel)."""
    n_slots, blocks_per_slot = table.shape
    g = jnp.take(pool, table.reshape(-1), axis=0)
    g = g.reshape((n_slots, blocks_per_slot) + pool.shape[1:])
    g = jnp.moveaxis(g, 1, 2)
    return g.reshape(n_slots, pool.shape[1],
                     blocks_per_slot * pool.shape[2], pool.shape[3])


def _page_append(pool, table, lengths, kv, block_size):
    """Write one new KV position per slot into its current tail block
    (kokkos.page_append)."""
    rows = jnp.arange(table.shape[0])
    blk = table[rows, lengths // block_size]
    off = lengths % block_size
    return pool.at[blk, :, off, :].set(kv)


def _page_copy(dst, src, src_ids, dst_ids, block_size):
    """Copy whole KV blocks between arenas (kokkos.page_copy — CoW forks
    and the preemption/swap tier); arenas are rank 4 or rank 5, with the
    block axis at ndim-4."""
    axis = dst.ndim - 4
    taken = jnp.take(src, src_ids, axis=axis).astype(dst.dtype)
    idx = (slice(None),) * axis + (dst_ids,)
    return dst.at[idx].set(taken)


def _batch_norm(x, s, b, m, v, *, eps):
    inv = s * jax.lax.rsqrt(v + eps)
    return x * inv[None, :, None, None] + (b - m * inv)[None, :, None, None]


_initialized = False
_WEIGHTS = {}


def lapis_initialize():
    """Load embedded weights onto the device (paper §4.4: generated
    lapis_initialize allocates and populates globally scoped Views)."""
    global _initialized
    if _initialized:
        return
    blob = base64.b64decode(_WEIGHTS_B64)
    with np.load(io.BytesIO(blob)) as z:
        for k in z.files:
            _WEIGHTS[k] = jax.device_put(z[k])
    _initialized = True


def lapis_finalize():
    global _initialized
    _WEIGHTS.clear()
    _initialized = False
'''


def emit_python_source(graph: Graph,
                       options: Optional[CompileOptions] = None) -> str:
    """Emit a freestanding Python module implementing ``graph``."""
    options = options or current_options()
    names = ValueNamer()
    names.bind_inputs(graph)
    consts: dict = {}
    body = []

    for op in graph.ops:
        if op.opname in ("kokkos.sync", "kokkos.modify"):
            val = names[op.operands[0].id]
            space = op.attrs.get("space", "device")
            body.append(f"# {op.opname} {val} {{{space}}} — lazy h2d on "
                        "first use (weights loaded by lapis_initialize)")
            continue
        if op.regions:
            # kokkos.fused — or a kokkos.*_parallel nest lowered from one:
            # re-emit the structured sub-op chain the region records
            body.extend(_fused_region_lines(op, names))
            continue
        for r in op.results:
            names.bind_fresh(r)
        if op.opname == "tensor.constant":
            value = np.asarray(op.attrs["value"])
            res = names[op.results[0].id]
            if value.ndim == 0:
                # paper §4.4: scalar constants are inlined as literals so
                # the device compiler sees them (no host propagation)
                body.append(f"{res} = jnp.asarray({value.item()!r}, "
                            f"dtype=jnp.{value.dtype.name})")
            else:
                key = constant_label(len(consts))
                consts[key] = value
                body.append(f"{res} = _WEIGHTS[{key!r}]")
            continue
        if op.opname in ("kokkos.range_parallel", "kokkos.team_parallel"):
            # source path uses library semantics for parallel nests: emit
            # the original tensor-level op recorded in attrs["src"]
            # (attr-aware ops like softmax go through _src_line via a
            # proxy op)
            src_name = op.attrs.get("src", "")
            fn_src = _SRC_OPS.get(src_name)
            a = [names[o.id] for o in op.operands]
            res = names[op.results[0].id]
            if fn_src is not None:
                body.append(f"{res} = {fn_src.format(*a)}")
            else:
                proxy = Op(src_name, op.operands,
                           [r.type for r in op.results],
                           attrs={k: v for k, v in op.attrs.items()
                                  if k not in ("fn", "tiling", "kind",
                                               "iter_space", "level_map",
                                               "nest", "exec_space",
                                               "collapse", "src", "ops",
                                               "cost")})
                for pr, rr in zip(proxy.results, op.results):
                    names[pr.id] = names[rr.id]
                body.append(_src_line(proxy, names))
            continue
        body.append(_src_line(op, names))

    outs = ", ".join(names[v.id] for v in graph.outputs)
    args = ", ".join(names[v.id] for v in graph.inputs)
    fn_src = [f"def {graph.name}({args}):",
              "    lapis_initialize()"]
    fn_src += ["    " + line for line in body]
    fn_src.append(f"    return {outs}")

    buf = io.BytesIO()
    np.savez(buf, **consts)
    blob = base64.b64encode(buf.getvalue()).decode("ascii")
    blob_lines = textwrap.wrap(blob, 79 - 4)
    blob_src = "_WEIGHTS_B64 = (\n" + "\n".join(
        f'    "{l}"' for l in blob_lines) + "\n)"
    prelude = _PRELUDE
    # between-pass analysis diagnostics ride into the emitted module as
    # comments (only attached by verifying compiles — plain emits are
    # byte-identical to before)
    diags = getattr(graph, "diagnostics", ())
    if diags:
        prelude += "\n" + "\n".join(f"# analysis: {d.format()}"
                                    for d in diags)
    return "\n\n".join([prelude, blob_src, "\n".join(fn_src), ""])
