"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body **once**, so any
``lax.scan`` (layer stacks, microbatches, GRUs, attention chunks) makes the
module-level flops/bytes a large undercount.  This analyzer re-derives both
from the compiled HLO text, multiplying loop bodies by their trip counts:

  * flops: ``dot``/``convolution`` ops (2 * prod(out) * prod(contract)),
    recursing through fusions / calls / while bodies;
  * bytes: HloCostAnalysis-like (operands + outputs per op, fusions at the
    call boundary), times trip counts;
  * collective bytes: per kind, raw + ring-factor wire estimates, times trip
    counts.

Trip counts come from the canonical scan condition (the max integer constant
in the ``while`` condition computation).  Validated against unrolled-scan
ground truth in tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import dataclasses
import re

_DT_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_SKIP_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"((?:f|bf|s|u|pred|c|token)[\w]*)\[([\d,]*)\]")
# a tuple type may hold one level of parentheses: TPU layouts such as
# ``f32[8]{0:T(1024)}``
_DEF_RE = re.compile(
    r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[\w]+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\("
)
_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES.get(dt, 4)
    return total


def _shape_elems(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _first_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Costs:
    flops: float = 0.0
    bytes: float = 0.0  # upper bound: every op's operands+outputs (unfused)
    bytes_min: float = 0.0  # lower bound: dot/conv/gather traffic only
    coll: dict = dataclasses.field(default_factory=dict)
    wire: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "Costs", mult: float = 1.0,
            bytes_too: bool = True) -> None:
        self.flops += other.flops * mult
        if bytes_too:
            self.bytes += other.bytes * mult
        self.bytes_min += other.bytes_min * mult
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v * mult
        for k, v in other.wire.items():
            self.wire[k] = self.wire.get(k, 0.0) + v * mult


def _split_computations(text: str) -> tuple[dict, str | None]:
    comps: dict[str, list[str]] = {}
    cur: list[str] | None = None
    name = None
    entry = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _HDR_RE.match(stripped)
            if m:
                name = m.group(2)
                if m.group(1):
                    entry = name
                cur = []
        else:
            if stripped.startswith("}"):
                comps[name] = cur
                cur = None
            else:
                cur.append(stripped)
    return comps, entry


def _operands(line: str) -> list[str]:
    """Operand %names of an op line (top-level args of the first call)."""
    inner = line.split("(", 1)[1]
    # cut at the matching close paren
    depth, end = 1, len(inner)
    for i, ch in enumerate(inner):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return re.findall(r"%([\w.\-]+)", inner[:end])


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=(\{\{[^}]*\}|\[[\d,]+\]<=\[\d+\])", line)
    if not m:
        return 2
    groups = m.group(1)
    if groups.startswith("{{"):
        return groups[2:].split("}")[0].count(",") + 1
    inner = [int(d) for d in groups[1:].split("]")[0].split(",")]
    prod = 1
    for d in inner:
        prod *= d
    return max(prod // max(inner[0], 1), 2)


def _trip_count(cond_lines: list[str]) -> float:
    consts = []
    for line in cond_lines:
        for mc in re.finditer(r"constant\((\d+)\)", line):
            consts.append(int(mc.group(1)))
    return float(max(consts)) if consts else 1.0


def analyze_hlo(text: str) -> dict:
    comps, entry = _split_computations(text)
    memo: dict[str, Costs] = {}

    def _param_touched(comp_name: str) -> dict[int, float]:
        """For a fused computation: parameter index -> bytes actually read,
        when the parameter is only consumed through (dynamic-)slice ops.
        Prevents counting a scanned layer-stack at full size per iteration."""
        lines = comps.get(comp_name, ())
        pname: dict[str, int] = {}
        ltypes: dict[str, str] = {}
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            ltypes[m.group(1)] = m.group(2)
            if m.group(3) == "parameter":
                mi = re.search(r"parameter\((\d+)\)", line)
                if mi:
                    pname[m.group(1)] = int(mi.group(1))
        touched: dict[int, float] = {}
        for nm, idx in pname.items():
            sizes, ok = [], True
            for line in lines:
                m = _DEF_RE.match(line)
                if not m or f"%{nm}" not in line.split("(", 1)[-1]:
                    continue
                if m.group(1) == nm:
                    continue
                op = m.group(3)
                if op in ("dynamic-slice", "slice", "gather"):
                    # only the selected rows/slices are read
                    sizes.append(_shape_bytes(m.group(2)))
                elif op == "dynamic-update-slice":
                    # in-place window write: update-sized traffic, not full
                    ops_ = _operands(line)
                    upd = ops_[1] if len(ops_) > 1 else None
                    sizes.append(
                        2.0 * _shape_bytes(ltypes.get(upd, "f32[]"))
                        if upd else _shape_bytes(m.group(2))
                    )
                else:
                    ok = False
                    break
            if ok and sizes:
                touched[idx] = sum(sizes)
        return touched

    def comp_cost(name: str) -> Costs:
        if name in memo:
            return memo[name]
        memo[name] = Costs()  # cycle guard
        lines = comps.get(name, ())
        # symbol table: %name -> type string
        types: dict[str, str] = {}
        for line in lines:
            m = _DEF_RE.match(line)
            if m:
                types[m.group(1)] = m.group(2)
        total = Costs()
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            out_name, out_type, op = m.groups()
            if op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", line)
                mc = re.search(r"condition=%?([\w.\-]+)", line)
                if mb and mc:
                    trips = _trip_count(comps.get(mc.group(1), []))
                    total.add(comp_cost(mb.group(1)), trips)
                    total.add(comp_cost(mc.group(1)), trips)
                continue
            if op in ("fusion", "call", "conditional", "custom-call",
                      "async-start"):
                for mcall in re.finditer(
                    r"(?:calls=|to_apply=)%?([\w.\-]+)", line
                ):
                    total.add(comp_cost(mcall.group(1)), 1.0, bytes_too=False)
                mbr = re.search(r"branch_computations=\{([^}]*)\}", line)
                if mbr:
                    subs = re.findall(r"%?([\w.\-]+)", mbr.group(1))
                    if subs:
                        worst = max(
                            (comp_cost(s) for s in subs),
                            key=lambda c: c.flops,
                        )
                        total.add(worst, 1.0, bytes_too=False)
            if op == "dot":
                out_elems = _shape_elems(out_type)
                ops_ = _operands(line)
                mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
                k = 1
                if ops_ and mc and ops_[0] in types:
                    lhs_dims = _first_dims(types[ops_[0]])
                    for ci in mc.group(1).split(","):
                        if ci and int(ci) < len(lhs_dims):
                            k *= lhs_dims[int(ci)]
                total.flops += 2.0 * out_elems * k
            elif op == "convolution":
                out_elems = _shape_elems(out_type)
                ops_ = _operands(line)
                macs = 1
                if len(ops_) > 1 and ops_[1] in types:
                    kdims = _first_dims(types[ops_[1]])
                    if kdims:
                        ksz = 1
                        for d in kdims:
                            ksz *= d
                        macs = max(ksz // max(kdims), 1)  # / out-features
                total.flops += 2.0 * out_elems * macs
            # bytes: output + operands (HloCostAnalysis-style), with sliced
            # params attributed at their touched size
            if op not in _SKIP_BYTES:
                b = _shape_bytes(out_type)
                touched: dict[int, float] = {}
                if op == "fusion":
                    mcal = re.search(r"calls=%?([\w.\-]+)", line)
                    if mcal:
                        touched = _param_touched(mcal.group(1))
                ops_list = _operands(line)
                if op in ("dynamic-slice", "slice", "gather"):
                    b += _shape_bytes(out_type)  # read ~= output size
                else:
                    for i, o in enumerate(ops_list):
                        if o in types:
                            full = _shape_bytes(types[o])
                            b += min(full, touched.get(i, full))
                total.bytes += b
                # lower bound ("perfect fusion"): count only ops that must
                # touch HBM — matmul/conv operands, gathers, windowed cache
                # updates, collectives
                if op in ("dot", "convolution", "gather", "dynamic-slice",
                          "dynamic-update-slice", "scatter") or op.startswith(
                    tuple(_COLLECTIVES)
                ):
                    total.bytes_min += b
            # collectives
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES:
                size = _shape_bytes(out_type)
                g = _group_size(line)
                factor = {
                    "all-reduce": 2.0 * (g - 1) / g,
                    "all-gather": (g - 1) / g,
                    "reduce-scatter": float(g - 1),
                    "all-to-all": (g - 1) / g,
                    "ragged-all-to-all": (g - 1) / g,
                    "collective-permute": 1.0,
                }[base]
                total.coll[base] = total.coll.get(base, 0.0) + size
                total.wire[base] = total.wire.get(base, 0.0) + size * factor
        memo[name] = total
        return total

    if entry is None:
        entry = max(comps, key=lambda k: len(comps[k])) if comps else ""
    c = comp_cost(entry)
    # entry arguments + outputs always cross HBM once
    entry_io = 0.0
    for line in comps.get(entry, ()):
        m = _DEF_RE.match(line)
        if m and m.group(3) == "parameter":
            entry_io += _shape_bytes(m.group(2))
    return {
        "flops": c.flops,
        "bytes": c.bytes,
        "bytes_min": c.bytes_min + entry_io,
        "collective_raw": dict(c.coll),
        "collective_wire": dict(c.wire),
        "collective_wire_total": sum(c.wire.values()),
    }
