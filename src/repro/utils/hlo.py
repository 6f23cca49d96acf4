"""HLO text parsing: collective traffic accounting.

``cost_analysis()`` does not expose collective bytes, so we parse the
compiled HLO and sum the operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute. Sizes are computed from the
result shape strings (e.g. ``bf16[16,1024,4096]``).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# matches e.g. "  %x = bf16[2,16,128]{2,1,0} all-gather(...)" and tuple results
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^\s]*\s*,?\s*)+)\s*(?:\))?\s*"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->", re.M)
_WHILE_RE = re.compile(
    r"while\(([^)]*)\),\s*condition=%([\w.\-]+),\s*body=%([\w.\-]+)"
)
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str):
    """→ {computation_name: body_text} including the ENTRY computation.

    Computation headers sit at column 0 (instructions are indented):
      ``%name (params...) -> type {``  /  ``ENTRY %name (...) -> ... {``
    Params may contain nested tuple parens, so we only key off the leading
    token."""
    comps = {}
    cur_name, cur_lines = None, []
    for line in hlo_text.splitlines():
        is_header = (
            line
            and not line[0].isspace()
            and "{" in line
            and ("->" in line)
            and (line.startswith("%") or line.startswith("ENTRY"))
        )
        if is_header:
            if cur_name:
                comps[cur_name] = "\n".join(cur_lines)
            tok = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
            cur_name = tok.lstrip("%")
            cur_lines = [line]
        elif cur_name:
            cur_lines.append(line)
    if cur_name:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _trip_count(cond_text: str) -> int:
    """Loop bound = the largest integer constant in the condition computation
    (the induction-variable compare); 1 if none found (conservative)."""
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def loop_aware_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Collective bytes with while-loop trip multipliers.

    XLA's cost_analysis (and a naive text scan) count a scan body once; this
    walks ENTRY → while bodies, multiplying each computation's collectives by
    the product of enclosing trip counts. Needed because every per-layer
    collective sits inside the layer scan × microbatch scan.
    """
    comps = _split_computations(hlo_text)
    entry = None
    for name in comps:
        if "main" in name:
            entry = name
            break
    if entry is None and comps:
        entry = list(comps)[-1]

    totals: Dict[str, float] = defaultdict(float)
    count = 0
    seen = set()

    def visit(name: str, mult: float):
        nonlocal count
        if name not in comps or (name, mult) in seen:
            return
        seen.add((name, mult))
        text = comps[name]
        for m in _OP_RE.finditer(text):
            shapes, kind = m.group(1), m.group(2)
            if f"{kind}-done" in m.group(0):
                continue
            totals[kind] += _shape_bytes(shapes) * mult
            count += 1
        for wm in _WHILE_RE.finditer(text):
            cond, body = wm.group(2), wm.group(3)
            trips = _trip_count(comps.get(cond, ""))
            visit(body, mult * trips)

    if entry:
        visit(entry, 1.0)
    out = {k: int(v) for k, v in totals.items()}
    out["total"] = int(sum(v for k, v in totals.items() if k in _COLLECTIVES))
    out["count"] = count
    return out


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """→ {op_kind: summed result bytes} + 'total' + 'count'.

    Bytes are per-SPMD-program (i.e. per device) since compiled HLO for SPMD
    is the single-device program.
    """
    out: Dict[str, int] = defaultdict(int)
    count = 0
    for m in _OP_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        # '-start' ops are paired with '-done'; count starts only
        if f"{kind}-done" in m.group(0):
            continue
        out[kind] += _shape_bytes(shapes)
        count += 1
    out["total"] = sum(v for k, v in out.items() if k in _COLLECTIVES)
    out["count"] = count
    return dict(out)
