"""FLOP analysis of compiled HLO text.

``jax``'s ``compiled.cost_analysis()`` is backend-dependent and, on CPU,
reports unrolled-loop flops inconsistently; this walker parses the module
text directly so the roofline benches get one deterministic number:

* ``dot`` flops are exact: 2 x |output| x contracted extent, with the lhs
  shape read inline where the text prints it and otherwise resolved from the
  instruction that defines the operand (newer XLA prints bare operand names);
* ``while`` bodies multiply by the trip count (XLA annotates compiled loops
  with ``backend_config={"known_trip_count":{"n":...}}``; a constant-bound
  ``compare(LT)`` condition is the fallback);
* ``fusion`` / ``call`` bodies are walked where they are called, so a scanned
  layer stack and its unrolled twin analyze to the same total.

``parse_module`` returns the computation table for ad-hoc inspection.
"""
from __future__ import annotations

import re
from typing import Dict, List

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+"?(\d+)')
_CALLED_RE = re.compile(r"(?:body|to_apply|calls|condition|branch_computations)="
                        r"[({]?%?([\w.\-]+)")
_DOT_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[a-z0-9]+\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")


def _dims(dim_str: str) -> List[int]:
    return [int(d) for d in dim_str.split(",") if d]


def parse_module(hlo_text: str) -> Dict[str, List[str]]:
    """Split module text into {computation_name: [instruction lines]}."""
    comps: Dict[str, List[str]] = {}
    entry = None
    current = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not line or line.startswith("//"):
            continue
        # Computation header: "[ENTRY ]%name (args...) -> result {"
        if line.endswith("{") and "->" in line and "=" not in line.split("->")[0]:
            parts = line.split()
            tok = parts[1] if parts[0] == "ENTRY" else parts[0]
            current = tok.lstrip("%")
            comps[current] = []
            if parts[0] == "ENTRY":
                entry = current
            continue
        if line == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(line)
    comps["__entry__"] = [entry] if entry else []
    return comps


def _shape_table(comps) -> Dict[str, List[int]]:
    """{instruction name: dims} over every computation (names are unique)."""
    table: Dict[str, List[int]] = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for line in lines:
            m = _DEF_RE.match(line)
            if m:
                table[m.group(1)] = _dims(m.group(2))
    return table


def _dot_flops(line: str, shapes: Dict[str, List[int]]) -> float:
    """2 x |out| x contracted extent; the lhs shape is read inline or looked
    up by the operand's name in ``shapes``."""
    lhs, _, rhs = line.partition("= ")
    out_shapes = _SHAPE_RE.findall(rhs.split("(", 1)[0])
    if not out_shapes:
        return 0.0
    out_elems = 1
    for d in _dims(out_shapes[0][1]):
        out_elems *= d
    args = rhs.split("(", 1)[1]
    first = args.split(",", 1)[0]
    typed = _SHAPE_RE.findall(first)
    named = _OPERAND_RE.search(first)
    if typed:
        lhs_dims = _dims(typed[0][1])
    elif named and named.group(1) in shapes:
        lhs_dims = shapes[named.group(1)]
    else:
        lhs_dims = None
    m = _DOT_CONTRACT_RE.search(line)
    if lhs_dims is None or not m:
        return 2.0 * out_elems  # degenerate: treat as elementwise-ish
    k = 1
    for idx in _dims(m.group(1)):
        if idx < len(lhs_dims):
            k *= lhs_dims[idx]
    return 2.0 * out_elems * k


def _line_flops(line: str, shapes: Dict[str, List[int]]) -> float:
    if re.search(r"= .*\bdot\(", line):
        return _dot_flops(line, shapes)
    if re.search(r"= .*\bconvolution\(", line):
        # Rare here (whisper stub conv): approximate from output size x window.
        out = _SHAPE_RE.findall(line.split("(", 1)[0])
        n = 1
        for d in _dims(out[0][1]) if out else []:
            n *= d
        return 2.0 * n
    return 0.0


def _trip_count(line: str, comps, cond_name) -> int:
    m = _TRIP_RE.search(line)
    if m:
        return int(m.group(1))
    # Fallback: condition of the form compare(iv, constant(N)), direction=LT.
    if cond_name and cond_name in comps:
        const, bound = None, None
        for ln in comps[cond_name]:
            c = re.search(r"constant\((\d+)\)", ln)
            if c:
                const = int(c.group(1))
            if "direction=LT" in ln:
                bound = const
        if bound is not None:
            return bound
    return 1


def _comp_flops(name: str, comps, memo, shapes) -> float:
    if name not in comps:
        return 0.0
    if name in memo:
        return memo[name]
    memo[name] = 0.0  # cycle guard
    total = 0.0
    for line in comps[name]:
        total += _line_flops(line, shapes)
        called = _CALLED_RE.findall(line)
        if not called:
            continue
        if re.search(r"= .*\bwhile\(", line):
            body = next((c for c in called if "cond" not in c), None)
            m = re.search(r"body=%?([\w.\-]+)", line)
            body = m.group(1) if m else body
            mc = re.search(r"condition=%?([\w.\-]+)", line)
            cond = mc.group(1) if mc else None
            trips = _trip_count(line, comps, cond)
            total += trips * _comp_flops(body, comps, memo, shapes)
        elif re.search(r"= .*\b(fusion|call|map|conditional|reduce|sort|scatter)\(", line):
            for c in called:
                total += _comp_flops(c, comps, memo, shapes)
    memo[name] = total
    return total


def analyze(hlo_text: str) -> dict:
    """Walk the module from ENTRY; returns {"flops", "dots", "whiles"}."""
    comps = parse_module(hlo_text)
    entry = comps.get("__entry__", [None])
    entry = entry[0] if entry else None
    if entry is None:
        return {"flops": 0.0, "dots": 0, "whiles": 0}
    flat = "\n".join("\n".join(v) for k, v in comps.items() if k != "__entry__")
    return {
        "flops": _comp_flops(entry, comps, {}, _shape_table(comps)),
        "dots": len(re.findall(r"= .*\bdot\(", flat)),
        "whiles": len(re.findall(r"= .*\bwhile\(", flat)),
    }
