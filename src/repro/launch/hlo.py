"""Post-optimization HLO text analysis: collective inventory with
while-loop trip-count scaling.

``compiled.as_text()`` exposes the final module. We extract every
``all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute``
op with its result bytes, then walk the computation call graph: ops inside a
``while`` body are multiplied by that loop's trip count (parsed from the
condition computation's comparison constant — scan lowers to
``i < trip_count``). Nested loops multiply.

This matters because the layer stack is a ``lax.scan``: its collectives
appear once in the HLO but execute L times. (Verified against an unrolled
reference in tests/test_hlo_parse.py.)
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, incl. tuples: '(bf16[2,3], f32[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes: int            # result bytes (single execution)
    trips: int            # enclosing loop multiplier
    computation: str
    line: str

    @property
    def total_bytes(self) -> int:
        return self.bytes * self.trips


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> list of body lines.

    HLO pretty-printing puts computation headers at zero indentation
    (``%name (params...) -> type {`` or ``ENTRY %name ...``) and op lines at
    two spaces. Splitting on indentation is robust to nested parens/brackets
    inside parameter type lists, which defeat regex-only header matching.
    """
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line:
            continue
        if line[0] not in " }":  # zero-indent: header or module junk
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                comps[cur] = []
            else:
                cur = None
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps


def _loop_bounds(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """while-body computation name -> trip count (best effort)."""
    bounds: Dict[str, int] = {}
    for cname, lines in comps.items():
        for line in lines:
            m = re.search(r"while\(.*?\)\s*,\s*condition=%?([\w\.\-]+)\s*,\s*body=%?([\w\.\-]+)", line)
            if not m:
                continue
            cond, body = m.group(1), m.group(2)
            trip = _parse_trip(comps.get(cond, []))
            bounds[body] = trip if trip is not None else 1
    return bounds


def _parse_trip(cond_lines: List[str]) -> Optional[int]:
    consts = []
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            consts.append(int(m.group(1)))
    # scan conditions compare the induction var against the trip count, which
    # is the largest integer constant in the tiny condition computation.
    return max(consts) if consts else None


def _call_edges(comps: Dict[str, List[str]]) -> Dict[str, List[Tuple[str, int]]]:
    """computation -> [(callee, multiplier)]: while bodies get their trip
    count, everything else (fusions, calls, conditionals) multiplier 1."""
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    bounds = _loop_bounds(comps)
    pat = re.compile(
        r"(?:condition|body|calls|to_apply|branch_computations)="
        r"(?:{([^}]*)}|%?([\w\.\-]+))")
    for cname, lines in comps.items():
        for line in lines:
            is_while = "while(" in line
            for m in pat.finditer(line):
                names = ([n.strip().lstrip("%") for n in m.group(1).split(",")]
                         if m.group(1) else [m.group(2)])
                for callee in names:
                    if callee not in comps:
                        continue
                    mult = bounds.get(callee, 1) if is_while else 1
                    edges[cname].append((callee, mult))
    return edges


def _entry_name(hlo: str, comps: Dict[str, List[str]]) -> Optional[str]:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo, re.MULTILINE)
    if m and m.group(1) in comps:
        return m.group(1)
    # fallback: computation not called by anyone
    called = {c for outs in _call_edges(comps).values() for c, _ in outs}
    for c in comps:
        if c not in called:
            return c
    return None


def collect_collectives(hlo: str) -> List[CollectiveOp]:
    comps = _split_computations(hlo)
    edges = _call_edges(comps)
    entry = _entry_name(hlo, comps)

    # multiplier per computation = product of loop trips along the call path
    mult: Dict[str, int] = defaultdict(int)

    def walk(c: str, m: int, depth=0):
        if depth > 50:
            return
        if mult[c] >= m:
            return
        mult[c] = max(mult[c], m)
        for callee, k in edges.get(c, []):
            walk(callee, m * k, depth + 1)

    if entry:
        walk(entry, 1)
    else:  # pragma: no cover - defensive
        for c in comps:
            mult[c] = 1

    ops: List[CollectiveOp] = []
    for cname, lines in comps.items():
        if mult.get(cname, 0) == 0:
            continue
        for line in lines:
            for kind in COLLECTIVES:
                # match ' = <shape> all-reduce(' exactly (not 'all-reduce-start')
                m = re.search(r"=\s*([^=]*?)\s+" + kind + r"(?:-start)?\(", line)
                if m:
                    ops.append(CollectiveOp(
                        kind=kind, bytes=shape_bytes(m.group(1)),
                        trips=max(mult.get(cname, 1), 1),
                        computation=cname, line=line[:160]))
                    break
    return ops


def collective_summary(hlo: str) -> Dict[str, int]:
    """kind -> total bytes (loop-scaled); plus 'total'."""
    out: Dict[str, int] = defaultdict(int)
    for op in collect_collectives(hlo):
        out[op.kind] += op.total_bytes
        out["total"] += op.total_bytes
    return dict(out)
