"""Post-SPMD HLO text analysis for the roofline.

``compiled.cost_analysis()`` counts a ``while`` (lax.scan) body **once**,
so a 64-layer scanned model under-reports flops/bytes/collectives by ~64×.
This module re-derives the three roofline inputs from the compiled HLO
text with **trip-count awareness**:

  1. split the module into computations;
  2. build the call graph (while body/condition, fusion `calls=`,
     `to_apply=`, conditionals) and a per-computation execution multiplier
     (entry = 1, while body = parent × trip count);
  3. FLOPs: every `dot` contributes 2 × |result| × Π(contracting dims)
     (batch dims are already in |result|); convolutions approximated;
  4. HBM bytes: per executed instruction, |result| + Σ|operands| — the
     HloCostAnalysis memory model where a fusion reads inputs once and
     writes outputs once (free ops skipped);
  5. collective bytes: Σ operand sizes per collective instruction, by op
     kind (assignment §Roofline).

All sizes are per-shard (post-SPMD shapes are per-device), matching the
per-chip roofline denominators.

CPU-backend caveat (EXPERIMENTS.md §Roofline): XLA:CPU float-normalizes
bf16 compute to f32, so compute-path tensors parse at twice their TPU
width.  We report raw parsed values; TPU-native estimates apply ×0.5 to
memory/collective terms on the bf16 compute path.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_FREE_OPS = {"tuple", "get-tuple-element", "parameter", "bitcast",
             "constant", "iota", "copy-done", "after-all", "partition-id",
             # control flow moves no HBM itself — bodies are counted
             "while", "conditional", "call"}


def _shapes_in(type_str: str) -> List[Tuple[str, int]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out.append((dt, n))
    return out


def _shape_bytes(type_str: str) -> int:
    return sum(n * _DTYPE_BYTES[dt] for dt, n in _shapes_in(type_str))


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


class Instruction:
    __slots__ = ("name", "rtype", "opcode", "operands", "rhs")

    def __init__(self, name, rtype, opcode, operands, rhs):
        self.name = name
        self.rtype = rtype
        self.opcode = opcode
        self.operands = operands
        self.rhs = rhs


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[\w\[\],\s{}\d]*?\)?)\s*"
    r"([\w\-]+)\((.*)$")


def parse_module(hlo_text: str) -> Dict[str, List[Instruction]]:
    comps: Dict[str, List[Instruction]] = {}
    current: Optional[str] = None
    entry: Optional[str] = None
    for raw in hlo_text.splitlines():
        # long tuple types carry /*index=N*/ comments — strip them
        line = re.sub(r"/\*.*?\*/", "", raw).rstrip()
        if current is None:
            # computation header: "<name> (params…) -> type {"  — the
            # param list may contain nested parens (tuple types), so match
            # structurally, not with one regex.
            s = line.strip()
            if s.endswith("{") and "->" in s and "=" not in s.split("->")[0]:
                toks = s.split()
                name = toks[1] if toks[0] == "ENTRY" and len(toks) > 1 \
                    else toks[0]
                name = name.lstrip("%").split("(")[0]
                if name and name != "HloModule":
                    current = name
                    comps[current] = []
                    if toks[0] == "ENTRY":
                        entry = current
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, opcode, rest = m.groups()
        # operand names: %tokens inside the first paren group
        depth, ops, tok = 1, [], ""
        for ch in rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    if tok.strip():
                        ops.append(tok.strip())
                    break
            if depth >= 1 and ch not in "()":
                if ch == "," and depth == 1:
                    ops.append(tok.strip())
                    tok = ""
                else:
                    tok += ch
        operands = [o.lstrip("%").split(" ")[0] for o in ops if o]
        comps[current].append(
            Instruction(name, rtype.strip(), opcode, operands, line))
    comps["__entry__"] = comps.get(entry, [])
    comps["__entry_name__"] = entry  # type: ignore
    return comps


def _attr(rhs: str, key: str) -> Optional[str]:
    m = re.search(rf"{key}=%?([\w.\-]+)", rhs)
    return m.group(1) if m else None


def _trip_count(cond_instrs: List[Instruction]) -> int:
    """Trip count from the while condition: the constant compared against
    the induction variable (falls back to the largest s32 constant)."""
    consts = {}
    for ins in cond_instrs:
        m = re.search(r"constant\((\d+)\)", ins.rhs)
        if m and ins.rtype.startswith("s32"):
            consts[ins.name] = int(m.group(1))
    for ins in cond_instrs:
        if ins.opcode == "compare":
            for op in ins.operands:
                if op in consts:
                    return max(consts[op], 1)
    return max(consts.values(), default=1)


def _multipliers(comps: Dict[str, List[Instruction]]
                 ) -> Tuple[Dict[str, float], set]:
    """→ (execution multiplier per computation, set of fused-body comps).
    Fused bodies execute as one kernel: their instructions count for
    FLOPs but not for HBM bytes (the call site's fusion model covers
    those)."""
    entry = comps.get("__entry_name__")
    mult: Dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    fused: set = set()
    # iterate to fixpoint over the call graph (it is a DAG)
    for _ in range(64):
        changed = False
        for comp, instrs in comps.items():
            if comp.startswith("__") or mult[comp] == 0.0:
                continue
            m = mult[comp]
            for ins in instrs:
                if ins.opcode == "while":
                    body = _attr(ins.rhs, "body")
                    cond = _attr(ins.rhs, "condition")
                    trips = _trip_count(comps.get(cond, []))
                    for target, factor in ((body, trips), (cond, trips + 1)):
                        if target and mult[target] < m * factor:
                            mult[target] = m * factor
                            changed = True
                elif ins.opcode in ("fusion", "call", "map", "reduce",
                                    "reduce-window", "scatter", "sort",
                                    "conditional", "custom-call",
                                    "async-start"):
                    for key in ("calls", "to_apply", "true_computation",
                                "false_computation", "branch_computations"):
                        t = _attr(ins.rhs, key)
                        if t and t in comps:
                            if ins.opcode != "conditional":
                                fused.add(t)
                            if mult[t] < m:
                                mult[t] = m
                                changed = True
        if not changed:
            break
    return mult, fused


def analyse_hlo(hlo_text: str) -> dict:
    """→ {"flops", "bytes", "collectives": {kind: bytes, "total": …},
    "collective_counts"} — trip-count-scaled, per-shard."""
    comps = parse_module(hlo_text)
    mult, fused_comps = _multipliers(comps)
    # symbol table: instruction name → result bytes (global; HLO names are
    # unique within a module dump)
    sizes: Dict[str, int] = {}
    types: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp.startswith("__"):
            continue
        for ins in instrs:
            sizes[ins.name] = _shape_bytes(ins.rtype)
            types[ins.name] = ins.rtype

    # parameter index map per computation (for the fusion byte model)
    params_of: Dict[str, Dict[int, str]] = {}
    uses_in: Dict[str, Dict[str, List[Instruction]]] = {}
    instrs_root: Dict[str, Instruction] = {}
    for comp, instrs in comps.items():
        if comp.startswith("__"):
            continue
        pmap: Dict[int, str] = {}
        umap: Dict[str, List[Instruction]] = defaultdict(list)
        for ins in instrs:
            if ins.opcode == "parameter":
                pm = re.match(r"\s*(\d+)", ins.rhs.split("parameter(")[-1])
                if pm:
                    pmap[int(pm.group(1))] = ins.name
            for o in ins.operands:
                umap[o].append(ins)
            if "ROOT" in ins.rhs.split("=")[0] or ins is instrs[-1]:
                instrs_root[comp] = ins
        params_of[comp] = pmap
        uses_in[comp] = umap

    def _instr_bytes(ins: Instruction) -> float:
        """HloCostAnalysis-style bytes-accessed for one instruction.
        Slicing ops touch slice-sized data, not their operands' full
        extent; fusions that only dynamic-slice a parameter internally
        charge the slice (the stacked scan-residual case)."""
        res = sizes.get(ins.name, 0)
        if ins.opcode == "dynamic-slice":
            return 2.0 * res
        if ins.opcode == "dynamic-update-slice":
            upd = sizes.get(ins.operands[1], 0) if len(ins.operands) > 1 \
                else res
            return 2.0 * upd
        if ins.opcode == "gather":
            return 2.0 * res
        if ins.opcode == "scatter":
            upd = sizes.get(ins.operands[-1], 0)
            return 2.0 * upd + res
        if ins.opcode == "fusion":
            comp_name = _attr(ins.rhs, "calls")
            total = float(res)
            pmap = params_of.get(comp_name, {})
            umap = uses_in.get(comp_name, {})

            def effective_bytes(name, depth=0):
                """Bytes actually read from a buffer reached only through
                slicing/aliasing ops (transitive through bitcasts)."""
                puses = umap.get(name, [])
                if not puses or depth > 4:
                    return None          # unknown → caller charges full
                tot = 0
                for u in puses:
                    if u.opcode in ("bitcast", "reshape", "copy"):
                        sub = effective_bytes(u.name, depth + 1)
                        if sub is None:
                            return None
                        tot += sub
                    elif u.opcode in ("dynamic-slice", "slice", "gather"):
                        tot += sizes.get(u.name, 0)
                    elif u.opcode == "dynamic-update-slice" and \
                            u.operands and u.operands[0] == name:
                        # read-modify-write touches only the update region
                        tot += sizes.get(u.operands[1], 0) \
                            if len(u.operands) > 1 else 0
                    else:
                        return None
                return tot

            for j, op in enumerate(ins.operands):
                opb = sizes.get(op, 0)
                pname = pmap.get(j)
                if pname:
                    eff = effective_bytes(pname)
                    if eff is not None:
                        opb = min(opb, eff)
                total += opb
            # a fusion whose ROOT is a dynamic-update-slice writes only the
            # update region, and its result aliases the input buffer
            root = instrs_root.get(comp_name)
            if root is not None and root.opcode == "dynamic-update-slice":
                upd = sizes.get(root.operands[1], 0) \
                    if len(root.operands) > 1 else 0
                total += upd - res       # replace full-result write
            return max(total, 0.0)
        return float(res + sum(sizes.get(o, 0) for o in ins.operands))

    flops = 0.0
    hbm = 0.0
    coll: Dict[str, float] = defaultdict(float)
    counts: Dict[str, int] = defaultdict(int)
    top_bytes: List[Tuple] = []
    top_coll: List[Tuple] = []
    for comp, instrs in comps.items():
        if comp.startswith("__"):
            continue
        m = mult.get(comp, 0.0)
        if m == 0.0:
            continue
        in_fused = comp in fused_comps
        for ins in instrs:
            if ins.opcode in _FREE_OPS:
                continue
            if not in_fused:    # fused-body bytes covered at the call site
                b = m * _instr_bytes(ins)
                hbm += b
                top_bytes.append((b, ins.opcode, comp, ins.name))
            if ins.opcode == "dot":
                res = 1
                for d in _shape_dims(ins.rtype):
                    res *= d
                lhs_t = types.get(ins.operands[0], "") if ins.operands \
                    else ""
                lhs_dims = _shape_dims(lhs_t)
                cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                                  ins.rhs)
                k = 1
                if cdims and lhs_dims:
                    for ci in cdims.group(1).split(","):
                        if ci:
                            k *= lhs_dims[int(ci)]
                flops += m * 2.0 * res * k
            elif ins.opcode == "convolution":
                res = 1
                for d in _shape_dims(ins.rtype):
                    res *= d
                rhs_t = types.get(ins.operands[1], "") \
                    if len(ins.operands) > 1 else ""
                kdims = _shape_dims(rhs_t)
                kelems = 1
                for d in kdims[:-1]:      # exclude output-feature dim
                    kelems *= d
                flops += m * 2.0 * res * kelems
            kind = None
            base = ins.opcode.replace("-start", "")
            if base in _COLLECTIVES:
                kind = base
            if kind:
                nbytes = sum(sizes.get(o, 0) for o in ins.operands) \
                    or sizes.get(ins.name, 0)
                coll[kind] += m * nbytes
                counts[kind] += 1
                top_coll.append((m * nbytes, kind, comp, ins.name, m))
    out = {k: float(v) for k, v in coll.items()}
    out["total"] = float(sum(coll.values()))
    top_bytes.sort(reverse=True)
    top_coll.sort(reverse=True)
    return {"flops": flops, "bytes": hbm, "collectives": out,
            "collective_counts": dict(counts),
            "top_bytes": top_bytes[:25], "top_collectives": top_coll[:25]}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Back-compat wrapper: collective byte totals (trip-count-scaled)."""
    r = analyse_hlo(hlo_text)
    d = dict(r["collectives"])
    d["counts"] = r["collective_counts"]
    return d


def kernel_calls(hlo_text: str) -> Dict[str, int]:
    """Pallas kernels that compiled to Mosaic custom calls, counted by
    kernel name.  Every kernel's ``pallas_call`` carries a ``name=``,
    which lands in the op_name metadata as ``.../<name>/pallas_call``; a
    kernel run in interpret mode, or an op that took the library path,
    leaves no ``tpu_custom_call`` at all."""
    counts: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?(\w+)/pallas_call', line)
        counts[m.group(1) if m else "?"] += 1
    return dict(counts)
