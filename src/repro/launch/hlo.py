"""HLO-text analysis: collective-bytes accounting.

Parses ``compiled.as_text()`` (post-SPMD, per-device module) and sums the
bytes of every collective op:

    all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute

Bytes = the op's OUTPUT shape bytes (operand bytes for these ops equal the
output except all-gather, where the output is the gathered extent — the
amount that actually crosses links per device; ring-algorithm per-link
traffic factors are not applied).

While-loop bodies (lax.scan over layers / microbatches) appear ONCE in the
text but execute trip-count times; ``collective_bytes`` walks the
computation call graph and multiplies each computation's bytes by the
product of enclosing while trip counts, read from the loop's
``backend_config={"known_trip_count":{"n":...}}`` annotation.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Dict, List, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls=|to_apply=|body=)%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_COLL_RE = re.compile(
    r"=\s*(.*?)\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string (handles tuples):
    'f32[16,128]{1,0}' -> 8192; '(f32[2], bf16[4])' -> 16."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    """-> ({computation name: instruction lines}, entry computation name)."""
    comps: Dict[str, List[str]] = {}
    cur, entry = None, None
    for line in hlo.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m and cur is None:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None and line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Per-collective-type bytes (per device, trip-count weighted)."""
    comps, entry = split_computations(hlo)

    direct: Dict[str, Dict[str, float]] = {}
    children: Dict[str, List[Tuple[str, float]]] = {}
    for name, lines in comps.items():
        d: Dict[str, float] = defaultdict(float)
        ch: List[Tuple[str, float]] = []
        for line in lines:
            s = line.strip()
            cm = _COLL_RE.search(s)
            if cm and ("-done(" not in s):   # count start/plain once, not done
                d[cm.group(2)] += shape_bytes(cm.group(1))
            if " while(" in s or s.startswith("while("):
                tm = _TRIP_RE.search(s)
                trips = float(tm.group(1)) if tm else 1.0
                bm = re.search(r"body=%?([\w\.\-]+)", s)
                if bm:
                    ch.append((bm.group(1), trips))
                continue
            for m in _CALL_RE.finditer(s):
                ch.append((m.group(1), 1.0))
            bm = _BRANCH_RE.search(s)
            if bm:
                for b in bm.group(1).split(","):
                    ch.append((b.strip().lstrip("%"), 1.0))
        direct[name] = dict(d)
        children[name] = ch

    memo: Dict[str, Dict[str, float]] = {}

    def total(name: str, stack=frozenset()) -> Dict[str, float]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return {}
        out = defaultdict(float, direct.get(name, {}))
        for child, mult in children.get(name, []):
            for k, v in total(child, stack | {name}).items():
                out[k] += mult * v
        memo[name] = dict(out)
        return memo[name]

    if entry is None:
        out: Dict[str, float] = defaultdict(float)
        for d in direct.values():
            for k, v in d.items():
                out[k] += v
        return dict(out)
    return dict(total(entry))


def collective_summary(hlo: str) -> Dict[str, float]:
    d = dict(collective_bytes(hlo))
    d["total"] = float(sum(d.values()))
    return d


def count_ops(hlo: str, opname: str) -> int:
    return len(re.findall(rf"=\s*[^=]*\b{opname}\(", hlo))


# --------------------------------------------------------------- HBM bytes
# Ops that move no data (aliases / bookkeeping).
_FREE_OPS = {"tuple", "get-tuple-element", "parameter", "bitcast", "constant",
             "after-all", "opt-barrier", "partition-id", "replica-id"}

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r"^((?:\([^=]*?\)|[\w\[\]\{\},\d\.]+)\s+)?([\w\-]+)\(")
_REF_RE = re.compile(r"%([\w\.\-]+)")
_PARAM_RE = re.compile(r"([\w\.\-]+)\s*:\s*((?:\([^)]*\)|[\w\[\]\{\},\d]+))")
_GROUPSZ_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_GROUPSET_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _header_params(header: str) -> Dict[str, int]:
    """'%f (p0: f32[2,4], p1: (f32[2], s32[]))' -> {name: bytes}."""
    out = {}
    inner = header[header.find("(") + 1:]
    for name, shape in _PARAM_RE.findall(inner):
        out[name] = shape_bytes(shape)
    return out


def hbm_bytes(hlo: str) -> float:
    """Fusion-aware per-device HBM traffic estimate.

    Sums (output + operand) bytes over TOP-LEVEL instructions of the entry
    computation and control-flow computations (while bodies x trip count,
    conditional branches). Fusion-internal instructions are excluded — a
    fusion op's operands/outputs at the call site are the real traffic —
    which is what XLA's own fusion-naive 'bytes accessed' on CPU overstates.
    Alias-only ops (bitcast/tuple/gte/parameter/constant) are free."""
    comps, entry = split_computations(hlo)
    headers: Dict[str, Dict[str, int]] = {}
    # recover headers (split_computations drops them): re-scan text
    for line in hlo.splitlines():
        m = _COMP_HDR_RE.match(line.strip())
        if m:
            headers[m.group(2)] = _header_params(line)

    def comp_bytes(name: str, stack=frozenset()) -> float:
        if name not in comps or name in stack:
            return 0.0
        shape_of: Dict[str, int] = dict(headers.get(name, {}))
        # first pass: record each instruction's output bytes
        parsed = []
        for line in comps[name]:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            iname, rhs = m.group(1), m.group(2)
            om = _OPNAME_RE.match(rhs)
            if not om:
                continue
            out_shape = om.group(1) or ""
            op = om.group(2)
            shape_of[iname] = shape_bytes(out_shape)
            parsed.append((iname, op, rhs))
        total = 0.0
        for iname, op, rhs in parsed:
            if op in _FREE_OPS:
                continue
            if op == "while":
                tm = _TRIP_RE.search(rhs)
                trips = float(tm.group(1)) if tm else 1.0
                bm = re.search(r"body=%?([\w\.\-]+)", rhs)
                if bm:
                    total += trips * comp_bytes(bm.group(1), stack | {name})
                continue
            if op == "conditional":
                bm = _BRANCH_RE.search(rhs)
                if bm:
                    for b in bm.group(1).split(","):
                        total += comp_bytes(b.strip().lstrip("%"),
                                            stack | {name})
                continue
            # operand refs up to metadata junk: take the call parens content
            body = rhs[rhs.find("("):]
            cut = body.find("), ")
            operands = body if cut < 0 else body[:cut + 1]
            b = shape_of.get(iname, 0)
            for ref in _REF_RE.findall(operands):
                b += shape_of.get(ref, 0)
            total += b
        return total

    if entry is None:
        return 0.0
    return comp_bytes(entry)


def quadratic_traffic(hlo: str, min_dim: int = 2048,
                      pair: tuple = (-2, -1), second_min: int | None = None,
                      rank_min: int = 0,
                      exclude_last: frozenset = frozenset()) -> float:
    """HBM traffic attributable to attention-score-like tensors: operands/
    outputs whose dims at positions `pair` are >= (second_min, min_dim)
    ((..., Sq|bq, Sk) score matrices — second_min < min_dim catches the
    chunked-attention (..., bq, Sk) blocks too; pair=(-3,-2) catches the
    SSD intra-chunk (..., Q, Q, nh) masks). rank_min excludes rank-2/3
    lookalikes (logits, MLP activations).

    Models the Pallas kernel variants, which keep these tiles in VMEM:
    kernel_hbm = hbm_bytes - quadratic_traffic (q/k/v/o and everything
    else unchanged)."""
    comps, entry = split_computations(hlo)
    lo = min_dim if second_min is None else second_min

    def is_quadratic(shape_str: str) -> bool:
        m = _SHAPE_RE.search(shape_str or "")
        if not m:
            return False
        dims = [int(d) for d in m.group(2).split(",") if d]
        i, j = pair
        return (len(dims) >= max(rank_min, -i, -j)
                and dims[i] >= lo and dims[j] >= min_dim
                and dims[-1] not in exclude_last)

    def comp_traffic(name: str, stack=frozenset()) -> float:
        if name not in comps or name in stack:
            return 0.0
        qbytes: Dict[str, int] = {}
        parsed = []
        for line in comps[name]:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            iname, rhs = m.group(1), m.group(2)
            om = _OPNAME_RE.match(rhs)
            if not om:
                continue
            out_shape, op = om.group(1) or "", om.group(2)
            qbytes[iname] = shape_bytes(out_shape) if is_quadratic(out_shape) \
                else 0
            parsed.append((iname, op, rhs))
        total = 0.0
        for iname, op, rhs in parsed:
            if op in _FREE_OPS:
                continue
            if op == "while":
                tm = _TRIP_RE.search(rhs)
                trips = float(tm.group(1)) if tm else 1.0
                bm = re.search(r"body=%?([\w\.\-]+)", rhs)
                if bm:
                    total += trips * comp_traffic(bm.group(1), stack | {name})
                continue
            body = rhs[rhs.find("("):]
            cut = body.find("), ")
            operands = body if cut < 0 else body[:cut + 1]
            b = qbytes.get(iname, 0)
            for ref in _REF_RE.findall(operands):
                b += qbytes.get(ref, 0)
            total += b
        return total

    return comp_traffic(entry) if entry else 0.0


def collective_group_sizes(hlo: str) -> Dict[str, float]:
    """Mean collective group size per op type (for ring-traffic factors)."""
    out: Dict[str, list] = defaultdict(list)
    for line in hlo.splitlines():
        cm = _COLL_RE.search(line)
        if not cm or "-done(" in line:
            continue
        k = None
        g = _GROUPSZ_RE.search(line)
        if g:
            k = int(g.group(2))
        else:
            g = _GROUPSET_RE.search(line)
            if g:
                k = len(g.group(1).split(","))
        if k:
            out[cm.group(2)].append(k)
    return {t: sum(v) / len(v) for t, v in out.items() if v}
