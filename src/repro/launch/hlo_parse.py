"""Loop-aware cost model over optimized HLO text.

``jax.stages.Compiled.cost_analysis()`` sums instruction costs with every
computation counted ONCE — a scan-over-layers body therefore contributes a
single iteration.  For roofline terms we need trip-scaled totals, so this
module parses the HLO module text into computation blocks, walks the call
graph (while bodies ×trip count from ``backend_config known_trip_count``,
calls, conditionals), and accumulates:

  flops            — exact for dot ops from dimension numbers
  bytes            — fusion-level traffic: operands + result of every
                     non-free top-level instruction (fusion internals are
                     register/VMEM-resident, matching XLA's own model)
  collective bytes — operand sizes of all-gather / all-reduce /
                     reduce-scatter / all-to-all / collective-permute

each scaled by the product of enclosing loop trip counts.  Per-kind
collective tables feed the §Perf analysis (redundant-collective hunting).

DTYPE CORRECTION: the CPU backend upcasts bf16 model tensors to f32 before
GEMMs/collectives, so the ``bytes``/``collective`` fields scale f32 sizes by
0.5 (what native-bf16 TPU would move); ``*_raw`` keeps the uncorrected sums.
Genuinely-f32 tensors (optimizer masters, softmax stats) are under-counted by
the correction; they are a small share of traffic.

The parser is text-based (the AOT API exposes no structured HLO) and
tolerant: unknown opcodes contribute bytes but no flops.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "add-dependency", "partition-id", "replica-id", "iota",
}

# opcodes whose called computations execute as part of the caller's schedule
_TRAVERSE_OPS = {"while", "call", "conditional", "async-start"}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"([a-z]\w*?)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+((?:\([^)]*\))|(?:[a-z]\w*\[[\d,]*\](?:\{[^}]*\})?))\s+([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _sizes(type_str: str) -> tuple[float, float]:
    """(raw_bytes, corrected_bytes) over a possibly-tuple type string."""
    raw = corr = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES[dtype]
        raw += b
        corr += b * (0.5 if dtype == "f32" else 1.0)
    return raw, corr


def _elems(type_str: str) -> float:
    n_total = 0.0
    for _, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        n_total += n
    return n_total


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str  # operands + attrs (text after the open paren)

    def operand_names(self) -> list[str]:
        return _OPERAND_RE.findall(self.rest.split(")", 1)[0])


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list
    is_entry: bool = False


def parse_module(text: str) -> tuple[dict, str]:
    comps: dict[str, Computation] = {}
    entry = None
    cur: Computation | None = None
    for line in text.splitlines():
        if cur is None:
            if line.rstrip().endswith("{"):
                m = _COMP_HDR_RE.match(line)
                if m and "->" in line:
                    cur = Computation(m.group(2), [], is_entry=bool(m.group(1)))
                    comps[cur.name] = cur
                    if cur.is_entry:
                        entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            cur.instrs.append(Instr(mi.group(1), mi.group(2), mi.group(3), mi.group(4)))
    if entry is None and comps:
        entry = next(iter(comps))
    return comps, entry


def _dot_flops(instr: Instr, types: dict[str, str]) -> float:
    res_elems = _elems(instr.type_str)
    ops = instr.operand_names()
    if not ops:
        return 0.0
    m = _SHAPE_RE.search(types.get(ops[0], ""))
    if not m:
        return 0.0
    lhs_dims = [int(d) for d in m.group(2).split(",") if d]
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.rest)
    contraction = 1
    if mc:
        for d in mc.group(1).split(","):
            if d and int(d) < len(lhs_dims):
                contraction *= lhs_dims[int(d)]
    return 2.0 * res_elems * contraction


@dataclasses.dataclass
class ModuleCost:
    flops: float
    bytes_raw: float
    bytes: float  # dtype-corrected
    collective_raw: dict
    collective: dict  # dtype-corrected
    collective_count: dict
    loop_trips: dict  # while instr -> trip count

    @property
    def collective_bytes(self) -> float:
        return sum(self.collective.values())

    @property
    def collective_bytes_raw(self) -> float:
        return sum(self.collective_raw.values())


def _fusion_param_charges(comp: Computation) -> dict[int, float]:
    """Per-parameter corrected byte charges for a fused computation.

    A fusion operand that is only (dynamic-)sliced inside the fusion is read
    at the SLICE size, not the full operand size (the scan-over-layers cache
    stack would otherwise be charged in full for every per-layer slice).
    Returns {param_index: charged_bytes} for params that qualify.
    """
    params: dict[str, int] = {}
    for i in comp.instrs:
        if i.opcode == "parameter":
            m = re.match(r"\s*(\d+)", i.rest)
            if m:
                params[i.name] = int(m.group(1))
    if not params:
        return {}
    uses: dict[str, list] = {name: [] for name in params}
    for i in comp.instrs:
        if i.opcode == "parameter":
            continue
        for on in i.operand_names():
            if on in uses:
                uses[on].append(i)
    out: dict[int, float] = {}
    for name, idx in params.items():
        insts = uses[name]
        if insts and all(u.opcode in ("dynamic-slice", "slice") for u in insts):
            charged = 0.0
            for u in insts:
                _, cb = _sizes(u.type_str)
                charged += cb
            out[idx] = charged
    return out


def analyze(text: str) -> ModuleCost:
    comps, entry = parse_module(text)
    types: dict[str, str] = {}
    for c in comps.values():
        for i in c.instrs:
            types[i.name] = i.type_str

    charges_cache: dict[str, dict] = {}

    def fusion_charges(called: str):
        if called not in charges_cache:
            comp = comps.get(called)
            charges_cache[called] = _fusion_param_charges(comp) if comp else {}
        return charges_cache[called]

    convert_cache: dict[str, bool] = {}

    def is_convert_only(called: str) -> bool:
        """Fusions that ONLY convert dtype (wrapped_convert_*): pure bf16<->f32
        reconciliation synthesized by the CPU backend; native-bf16 TPUs never
        materialize them.  Excluded from corrected bytes (kept in raw)."""
        if called not in convert_cache:
            comp = comps.get(called)
            ok = False
            if comp:
                real = [i for i in comp.instrs if i.opcode not in _FREE_OPS]
                ok = bool(real) and all(i.opcode in ("convert", "copy", "bitcast-convert")
                                        for i in real)
            convert_cache[called] = ok
        return convert_cache[called]

    # multiplier propagation from entry through while/call/conditional
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    loop_trips: dict[str, int] = {}
    queue = [entry]
    visited_edges = set()
    while queue:
        cname = queue.pop(0)
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for i in comp.instrs:
            if i.opcode not in _TRAVERSE_OPS:
                continue
            attrs = i.rest
            if i.opcode == "while":
                mt = _TRIP_RE.search(attrs)
                trips = int(mt.group(1)) if mt else 1
                loop_trips[i.name] = trips
                mb = re.search(r"body=%?([\w.\-]+)", attrs)
                if mb and (cname, i.name, mb.group(1)) not in visited_edges:
                    visited_edges.add((cname, i.name, mb.group(1)))
                    mult[mb.group(1)] += m * trips
                    queue.append(mb.group(1))
            else:
                for key in ("to_apply", "branch_computations", "true_computation",
                            "false_computation", "called_computations"):
                    mk = re.search(key + r"=\{?%?([\w.\-,%\s]+?)\}?[,)]", attrs)
                    if not mk:
                        continue
                    for name in re.findall(r"[\w.\-]+", mk.group(1)):
                        if name in comps and (cname, i.name, name) not in visited_edges:
                            visited_edges.add((cname, i.name, name))
                            mult[name] += m
                            queue.append(name)

    flops = 0.0
    bytes_raw = bytes_corr = 0.0
    coll_raw: dict[str, float] = defaultdict(float)
    coll_corr: dict[str, float] = defaultdict(float)
    coll_count: dict[str, int] = defaultdict(int)

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for i in comp.instrs:
            if i.opcode in _FREE_OPS:
                continue
            rb, cb = _sizes(i.type_str)
            charges = {}
            convert_only = i.opcode == "convert"
            if i.opcode == "fusion":
                mk = re.search(r"calls=%?([\w.\-]+)", i.rest)
                if mk:
                    charges = fusion_charges(mk.group(1))
                    convert_only = is_convert_only(mk.group(1))
            ob_raw = ob_corr = 0.0
            for pos, on in enumerate(i.operand_names()):
                t = types.get(on)
                if not t:
                    continue
                if pos in charges:  # sliced-only fusion operand
                    ob_raw += charges[pos] * 2  # raw ~ 2x corrected (f32)
                    ob_corr += charges[pos]
                    continue
                r, c = _sizes(t)
                ob_raw += r
                ob_corr += c
            bytes_raw += m * (rb + ob_raw)
            if not convert_only:
                bytes_corr += m * (cb + ob_corr)
            if i.opcode == "dot":
                flops += m * _dot_flops(i, types)
            base = i.opcode.replace("-start", "")
            if base in COLLECTIVE_OPS and not i.opcode.endswith("-done"):
                raw = corr = 0.0
                for on in i.operand_names():
                    t = types.get(on)
                    if t:
                        r, c = _sizes(t)
                        raw += r
                        corr += c
                coll_raw[base] += m * raw
                coll_corr[base] += m * corr
                coll_count[base] += int(m)

    return ModuleCost(
        flops=flops, bytes_raw=bytes_raw, bytes=bytes_corr,
        collective_raw=dict(coll_raw), collective=dict(coll_corr),
        collective_count=dict(coll_count), loop_trips=loop_trips,
    )
