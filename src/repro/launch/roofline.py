"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md
§Roofline).

Three terms per (arch × shape × mesh) cell:

    compute    = HLO_FLOPs      / (peak_FLOP/s per chip)
    memory     = HLO_bytes      / (HBM bytes/s per chip)
    collective = coll_bytes/dev / (ICI bytes/s per link)

``compiled.cost_analysis()`` reports per-device FLOPs/bytes with while trip
counts applied.  Collective bytes are NOT in cost_analysis: we parse the
post-optimization HLO — every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute's shape, multiplied by the ring-algorithm
traffic factor and by the ``known_trip_count`` of every enclosing while
loop (scan bodies).

Caveat (recorded in DESIGN.md §8): XLA:CPU's SPMD partitioner may choose
different collective algorithms than TPU's, so the collective term is a
*structural estimate* (bytes over link bandwidth), not a measurement.

HLO text format assumptions (post-optimization HLO, verified against
jax 0.4.x and 0.9.0 / XLA:CPU):

- Instruction lines: ``[ROOT] %name = type{layout} op(...), attrs`` — the
  result type precedes the op name; operands may appear either bare
  (``dot(%a, %b)``) or with inlined operand types
  (``dot(f32[2,32,64]{2,1,0} %a, f32[64,64]{1,0} %b)``).  Both forms are
  accepted; layout suffixes may contain tiling annotations
  (``{1,0:T(8,128)}``).
- Computation headers start at column 0 and contain ``{`` plus either
  ``->`` or a leading ``ENTRY``.
- While loops carry ``body=%name`` / ``condition=%name`` and, when XLA
  could infer it, ``backend_config={"known_trip_count":{"n":"N"}}``.
- Nested calls are reachable via ``calls=``, ``to_apply=``, ``body=`` or
  ``branch_computations=`` attributes.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["HW_V5E", "parse_collective_bytes", "parse_dot_flops",
           "roofline_terms", "RooflineCell"]

# TPU v5e constants (assignment-specified)
HW_V5E = {
    "peak_bf16_flops": 197e12,     # FLOP/s per chip
    "hbm_bytes_per_s": 819e9,      # per chip
    "ici_bytes_per_s": 50e9,       # per link
}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ring-algorithm traffic factors (bytes moved per device / payload bytes)
_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Max element-shape bytes appearing in a type string (tuples -> max)."""
    best = 0
    for m in _SHAPE_RE.finditer(text):
        b = _DTYPE_BYTES[m.group(1)]
        dims = m.group(2)
        if dims:
            for d in dims.split(","):
                b *= int(d)
        best = max(best, b)
    return best


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> its instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line.startswith(" ") and ("{" in line) and ("->" in line or
                                                           line.startswith("ENTRY")):
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)", line.strip())
            cur = m.group(1) if m else None
            if line.strip().startswith("ENTRY"):
                comps["__entry__"] = comps.setdefault(m.group(1), [])
                comps["__entry_name__"] = m.group(1)  # type: ignore
            if cur is not None:
                comps.setdefault(cur, [])
        elif cur is not None and line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def parse_collective_bytes(hlo: str) -> Dict[str, Dict[str, float]]:
    """Per-device collective traffic by kind, while-trip aware.

    Returns {kind: {"count": n_instructions, "bytes": traffic_bytes}} where
    traffic includes the ring factor and all enclosing loop trip counts.
    """
    comps = _split_computations(hlo)
    entry_name = comps.get("__entry_name__")
    if not isinstance(entry_name, str):
        # fall back: pick computation containing " ROOT %tuple" with most lines
        entry_name = max((k for k in comps if isinstance(comps[k], list)),
                         key=lambda k: len(comps[k]))

    # computation -> [(callee, trips)]
    calls: Dict[str, List[Tuple[str, float]]] = {}
    # computation -> [(kind, bytes)]
    colls: Dict[str, List[Tuple[str, float]]] = {}

    while_re = re.compile(r"=\s*\(.*?\)\s*while\(|while\(")
    body_re = re.compile(r"body=%?([\w\.\-]+)")
    trip_re = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
    callee_re = re.compile(r"(?:calls|to_apply|body|branch_computations)="
                           r"\{?%?([\w\.\-]+)")
    coll_re = re.compile(r"=\s*([^=]*?)\b(all-gather|all-reduce|"
                         r"reduce-scatter|all-to-all|collective-permute)"
                         r"(?:-start)?\(")

    for name, lines in comps.items():
        if not isinstance(lines, list):
            continue
        for line in lines:
            mc = coll_re.search(line)
            if mc and "-done" not in line:
                kind = mc.group(2)
                nbytes = _shape_bytes(mc.group(1))
                colls.setdefault(name, []).append((kind, float(nbytes)))
            if "while(" in line:
                mb = body_re.search(line)
                mt = trip_re.search(line)
                trips = float(mt.group(1)) if mt else 1.0
                if mb:
                    calls.setdefault(name, []).append((mb.group(1), trips))
            elif "calls=" in line or "to_apply=" in line:
                mk = callee_re.search(line)
                if mk:
                    calls.setdefault(name, []).append((mk.group(1), 1.0))

    # DFS with multipliers (the call graph is a DAG)
    out: Dict[str, Dict[str, float]] = {k: {"count": 0, "bytes": 0.0}
                                        for k in _COLL_KINDS}
    seen_stack = set()

    def walk(comp: str, mult: float) -> None:
        if comp in seen_stack:  # defensive: no recursion in HLO
            return
        seen_stack.add(comp)
        for kind, nbytes in colls.get(comp, ()):
            out[kind]["count"] += mult
            out[kind]["bytes"] += mult * nbytes * _FACTOR[kind]
        for callee, trips in calls.get(comp, ()):
            walk(callee, mult * trips)
        seen_stack.discard(comp)

    walk(entry_name, 1.0)
    return out


def parse_dot_flops(hlo: str) -> float:
    """Total dot/convolution FLOPs per device, while-trip aware.

    XLA:CPU's ``cost_analysis()`` counts each while body ONCE (no trip
    multiplication — verified against scanned-layer models), so the
    compute roofline term must be derived by walking the HLO: for every
    ``dot`` instruction, FLOPs = 2 * prod(output shape) * contracted size,
    multiplied by the ``known_trip_count`` of every enclosing while loop.
    """
    comps = _split_computations(hlo)
    entry_name = comps.get("__entry_name__")
    if not isinstance(entry_name, str):
        entry_name = max((k for k in comps if isinstance(comps[k], list)),
                         key=lambda k: len(comps[k]))

    inst_re = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
    body_re = re.compile(r"body=%?([\w\.\-]+)")
    trip_re = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
    callee_re = re.compile(r"(?:calls|to_apply|body|branch_computations)="
                           r"\{?%?([\w\.\-]+)")
    # operands may carry an inlined ``dtype[dims]{layout}`` prefix
    # (post-optimization HLO in current XLA) or appear bare (older text)
    _op = r"(?:\w+\[[0-9,]*\](?:\{[^}]*\})?\s+)?%([\w\.\-]+)"
    dot_re = re.compile(r"\bdot\(" + _op + r",\s*" + _op + r"\)")
    contract_re = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

    calls: Dict[str, List[Tuple[str, float]]] = {}
    flops: Dict[str, float] = {}

    for name, lines in comps.items():
        if not isinstance(lines, list):
            continue
        shapes: Dict[str, List[int]] = {}
        # first pass: symbol table of output shapes
        pend: List[Tuple[str, str]] = []
        for line in lines:
            m = inst_re.match(line)
            if not m:
                continue
            iname, rhs = m.group(1), m.group(2)
            sm = _SHAPE_RE.search(rhs)
            if sm:
                dims = [int(x) for x in sm.group(2).split(",")] if sm.group(2) else []
                shapes[iname] = dims
            pend.append((iname, rhs))
        total = 0.0
        for iname, rhs in pend:
            dm = dot_re.search(rhs)
            if dm:
                out_dims = shapes.get(iname, [])
                lhs_dims = shapes.get(dm.group(1), [])
                cm = contract_re.search(rhs)
                k = 1
                if cm and cm.group(1):
                    for ci in cm.group(1).split(","):
                        ci = int(ci)
                        if ci < len(lhs_dims):
                            k *= lhs_dims[ci]
                out = 1
                for dd in out_dims:
                    out *= dd
                total += 2.0 * out * k
            if "while(" in rhs:
                mb = body_re.search(rhs)
                mt = trip_re.search(rhs)
                if mb:
                    calls.setdefault(name, []).append(
                        (mb.group(1), float(mt.group(1)) if mt else 1.0))
            elif "calls=" in rhs or "to_apply=" in rhs:
                mk = callee_re.search(rhs)
                if mk:
                    calls.setdefault(name, []).append((mk.group(1), 1.0))
        flops[name] = total

    seen = set()

    def walk(comp: str, mult: float) -> float:
        if comp in seen:
            return 0.0
        seen.add(comp)
        t = flops.get(comp, 0.0) * mult
        for callee, trips in calls.get(comp, ()):
            t += walk(callee, mult * trips)
        seen.discard(comp)
        return t

    return walk(entry_name, 1.0)


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   hw: Dict[str, float] = HW_V5E) -> Dict[str, float]:
    return {
        "compute_s": flops / hw["peak_bf16_flops"],
        "memory_s": hbm_bytes / hw["hbm_bytes_per_s"],
        "collective_s": coll_bytes / hw["ici_bytes_per_s"],
    }


@dataclass
class RooflineCell:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops: float
    useful_ratio: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """compute_term / max-term: 1.0 = compute-bound at peak."""
        return self.compute_s / max(self.bound_s, 1e-30)
