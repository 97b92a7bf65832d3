"""Multiplicity-aware FLOP / byte / collective counter over compiled HLO.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified:
an 8-iteration lax.scan reports 8x fewer flops than its unrolled twin), so
for scan-over-layers models both FLOPs and in-loop collective bytes are
wildly understated. This module parses ``compiled.as_text()`` (post-SPMD,
per-device module) and walks the call graph with multiplicities:

  * while ops carry ``backend_config={"known_trip_count":{"n":...}}`` —
    body and condition are multiplied by n;
  * fusion computations contribute FLOPs but not bytes (internal regs);
  * dots: 2 · prod(result dims) · prod(lhs contracting dims);
  * elementwise arithmetic: 1 flop per output element; reduce: per input
    element;
  * bytes: operands + result per top-level instruction (XLA convention);
  * collectives: result bytes per type, multiplicity-weighted.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4,
    "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "tanh", "exponential", "log", "negate", "abs", "sqrt", "rsqrt",
    "logistic", "sine", "cosine", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "sign", "atan2", "remainder", "expm1", "log1p",
    "cbrt", "erf",
}

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start",
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"((?:\([^=]*?\))|(?:[\w]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_PARAM_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"((?:\([^=]*?\))|(?:[\w]+\[[^\]]*\](?:\{[^}]*\})?))\s+parameter\(")


def shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    """(element count, bytes) of a (possibly tuple) shape string."""
    elems = 0
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    if elems == 0 and "[" not in shape_str:
        # scalar like "f32[]" handled above; bare scalar tokens:
        m = re.match(r"\(?(\w+)\b", shape_str)
        if m and m.group(1) in _DTYPE_BYTES:
            return 1, _DTYPE_BYTES[m.group(1)]
    return elems, nbytes


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    rest: str  # operands + attrs tail


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    shapes: Dict[str, str]  # instr/param name -> shape string


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in text.splitlines():
        # strip /*index=N*/ comments inside long tuple types: they contain
        # '=' and ')' characters that break the instruction grammar
        if "/*" in line:
            line = _COMMENT_RE.sub("", line)
        if not line.strip():
            continue
        if not line.startswith(" ") and ("->" in line) and line.rstrip(
                ).endswith("{"):
            m = _COMP_RE.match(line.strip())
            if m:
                cur = Computation(m.group(1), [], {})
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry_name = cur.name
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            name, shape, opcode, rest = m.groups()
            cur.instrs.append(Instr(name, shape, opcode, rest))
            cur.shapes[name] = shape
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


@dataclasses.dataclass
class Counts:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    unknown_trip_loops: int = 0

    def add(self, other: "Counts", mult: float = 1.0,
            count_bytes: bool = True) -> None:
        self.flops += other.flops * mult
        if count_bytes:
            self.bytes += other.bytes * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + v * mult
        self.unknown_trip_loops += other.unknown_trip_loops


_ZERO_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "conditional", "call", "after-all",
               "partition-id", "replica-id", "iota"}
# slicing ops touch only the slice, not the whole operand buffer
_SLICE_LIKE = {"dynamic-slice", "slice", "gather"}


def _instr_bytes(ins: Instr, comp: Computation, out_bytes: int) -> float:
    """HBM bytes touched by one top-level instruction.

    XLA-convention approximations: slicing ops read+write the slice;
    dynamic-update-slice reads+writes the update region (in-place buffer);
    scatter reads/writes the update region twice (read-modify-write);
    while/call/tuple plumbing is free (bodies counted separately);
    everything else reads its operands and writes its result.
    """
    if ins.opcode in _ZERO_BYTES:
        return 0.0
    if ins.opcode in _SLICE_LIKE:
        return 2.0 * out_bytes
    if ins.opcode in ("dynamic-update-slice", "scatter"):
        ops = _OPERAND_RE.findall(ins.rest.split(" metadata=")[0])
        upd_bytes = 0
        for opnd in ops[1:]:  # update operand(s); skip the big buffer
            _, b = shape_elems_bytes(comp.shapes.get(opnd, ""))
            upd_bytes += b
        return 2.0 * max(upd_bytes, 1)
    if ins.opcode in ("broadcast",):
        return float(out_bytes)
    ops = []
    for opnd in _OPERAND_RE.findall(ins.rest.split(" calls=")[0]
                                    .split(" metadata=")[0]):
        _, b = shape_elems_bytes(comp.shapes.get(opnd, ""))
        ops.append(b)
    if ins.opcode == "fusion" and "dynamic-update-slice" in ins.name:
        # in-place DUS fusion: the big buffer operand aliases the result;
        # only the update region moves
        return 2.0 * max(sum(ops) - max(ops, default=0), 1)
    return float(out_bytes + sum(ops))


def _dot_flops(instr: Instr, comp: Computation) -> float:
    out_elems, _ = shape_elems_bytes(instr.shape)
    ops = _OPERAND_RE.findall(instr.rest)
    k = 1.0
    m = _LHS_C_RE.search(instr.rest)
    if m and ops:
        lhs_shape = comp.shapes.get(ops[0], "")
        sm = _SHAPE_RE.search(lhs_shape)
        if sm:
            dims = [int(d) for d in sm.group(2).split(",") if d]
            for ci in m.group(1).split(","):
                if ci and int(ci) < len(dims):
                    k *= dims[int(ci)]
    return 2.0 * out_elems * k


def _analyze(comp: Computation, comps: Dict[str, Computation],
             memo: Dict[Tuple[str, bool], Counts],
             in_fusion: bool) -> Counts:
    key = (comp.name, in_fusion)
    if key in memo:
        return memo[key]
    c = Counts()
    for ins in comp.instrs:
        out_elems, out_bytes = shape_elems_bytes(ins.shape)
        # ---- bytes (only at non-fusion level) ----
        if not in_fusion:
            c.bytes += _instr_bytes(ins, comp, out_bytes)
        # ---- collectives ----
        if ins.opcode in _COLLECTIVES:
            base = ins.opcode.replace("-start", "")
            c.coll_bytes[base] = c.coll_bytes.get(base, 0.0) + out_bytes
        # ---- flops ----
        if ins.opcode == "dot":
            c.flops += _dot_flops(ins, comp)
        elif ins.opcode == "convolution":
            c.flops += 2.0 * out_elems  # lower bound (unused by our models)
        elif ins.opcode in _ELEMENTWISE or ins.opcode == "compare":
            c.flops += out_elems
        elif ins.opcode in ("reduce", "reduce-window"):
            ops = _OPERAND_RE.findall(ins.rest)
            if ops:
                e, _ = shape_elems_bytes(comp.shapes.get(ops[0], ""))
                c.flops += e
        # ---- callees ----
        if ins.opcode == "fusion":
            m = _CALLS_RE.search(ins.rest)
            if m and m.group(1) in comps:
                c.add(_analyze(comps[m.group(1)], comps, memo, True),
                      1.0, count_bytes=False)
        elif ins.opcode == "while":
            trip = 1
            tm = _TRIP_RE.search(ins.rest)
            if tm:
                trip = int(tm.group(1))
            else:
                c.unknown_trip_loops += 1
            for rx in (_BODY_RE, _COND_RE):
                m = rx.search(ins.rest)
                if m and m.group(1) in comps:
                    c.add(_analyze(comps[m.group(1)], comps, memo,
                                   in_fusion), float(trip))
        elif ins.opcode in ("call", "conditional", "async-start"):
            for m in _CALLS_RE.finditer(ins.rest):
                if m.group(1) in comps:
                    c.add(_analyze(comps[m.group(1)], comps, memo,
                                   in_fusion), 1.0)
    memo[key] = c
    return c


def count(hlo_text: str) -> Counts:
    comps = parse_module(hlo_text)
    if "__entry__" not in comps:
        return Counts()
    return _analyze(comps["__entry__"], comps, {}, False)


# ---------------------------------------------------------------------------
# Trace-level (jaxpr) primitive iteration/counting. Interpret-mode
# pallas_calls lower to plain HLO ops, so the kernel-launch regression guard
# ("one FNO block == one pallas_call", analysis/jaxpr_lint.py) must count at
# the jaxpr level, recursing through pjit / custom_vjp / scan / shard_map
# sub-jaxprs. Duck-typed (hasattr): sub-jaxprs hide under several param
# names and types, so nothing is imported from jax's core modules.
# ---------------------------------------------------------------------------
def iter_jaxpr_eqns(jaxpr, into_kernels: bool = True):
    """Yield every eqn of `jaxpr` and of all nested sub-jaxprs (pjit
    bodies, custom_vjp branches, scans, shard_map). into_kernels=False
    stops at pallas_call boundaries: the yielded stream is the
    LAUNCH-level op sequence (each pallas_call appears once; its kernel
    body is not expanded) — the level at which the fusion, cast-ownership,
    and collective contracts are stated (analysis/jaxpr_lint.py)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in eqn.params.values():
            yield from _iter_sub(v, into_kernels)


def _iter_sub(v, into_kernels):
    if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
        yield from iter_jaxpr_eqns(v.jaxpr, into_kernels)
    elif hasattr(v, "eqns"):  # Jaxpr
        yield from iter_jaxpr_eqns(v, into_kernels)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_sub(x, into_kernels)


def jaxpr_primitive_counts(fn, *args, into_kernels: bool = True,
                           **kwargs) -> Dict[str, int]:
    """{primitive name: count} over the full jaxpr of fn(*args), including
    every nested sub-jaxpr (pjit bodies, custom_vjp branches, scans).
    into_kernels=False stops at pallas_call boundaries — the remaining
    count is the LAUNCH-level op count (each pallas_call is one entry, its
    kernel body is not expanded), the fusion claim's "kernel calls"."""
    import jax
    counts: Dict[str, int] = {}
    for eqn in iter_jaxpr_eqns(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr,
                               into_kernels):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of pallas_call primitives fn(*args) traces to — the
    kernel-launch count of the fused path, robust to interpret mode."""
    return jaxpr_primitive_counts(fn, *args, **kwargs).get("pallas_call", 0)
