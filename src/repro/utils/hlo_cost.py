"""Exact cost extraction from compiled HLO text, fixing XLA's
``cost_analysis()`` blind spot: while-loop bodies are counted ONCE there,
so scan-over-layers programs under-report FLOPs and collective bytes by
the trip count. We rebuild the computation graph, propagate
``known_trip_count`` multipliers through while/call/fusion edges, and sum

  * dot FLOPs:      2 * prod(result dims) * prod(contracted dims)
  * collective wire bytes (ring-algorithm factors, see hlo_analysis)

per computation x effective multiplier.
"""
from __future__ import annotations

import re
from collections import defaultdict

from repro.utils.hlo_analysis import DTYPE_BYTES, _group_size

_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_SHAPE_RE = re.compile(r"^([a-z]\w*)\[([0-9,]*)\]")
_TUPLE_SHAPES = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")
# operands print bare ("dot(%a, %b)"); the result's layout braces can
# hold tiling suffixes like {1,0:T(8,128)(2,1)} on TPU
_OPERAND = r"%([\w.\-]+)"
_DOT_RE = re.compile(
    r"^([a-z]\w*)\[([0-9,]*)\][^=]*?\bdot\(" + _OPERAND + r",\s*"
    + _OPERAND + r"\)"
    r".*?lhs_contracting_dims=\{([0-9,]*)\}")
_WHILE_REF = re.compile(r"body=%?([\w.\-]+)")
_COND_REF = re.compile(r"condition=%?([\w.\-]+)")
_CALL_REFS = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_BRANCH_REFS = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')

COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")


def _dims(s: str) -> list[int]:
    return [int(d) for d in s.split(",") if d]


def parse_computations(text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in text.splitlines():
        m = _COMP_HDR.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
                continue
            comps[cur].append(line.strip())
    return comps


def _entry_name(text: str) -> str | None:
    for line in text.splitlines():
        ls = line.strip()
        if ls.startswith("ENTRY"):
            m = _COMP_HDR.match(ls)
            if m:
                return m.group(1)
    return None


def _multipliers(comps: dict[str, list[str]], entry: str) -> dict[str, float]:
    """Effective execution count per computation."""
    edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for name, lines in comps.items():
        for ls in lines:
            trip = 1.0
            mt = _TRIP_RE.search(ls)
            if mt:
                trip = float(mt.group(1))
            for m in _WHILE_REF.finditer(ls):
                edges[name].append((m.group(1), trip))
            for m in _COND_REF.finditer(ls):
                edges[name].append((m.group(1), trip + 1))
            for m in _CALL_REFS.finditer(ls):
                edges[name].append((m.group(1), 1.0))
            mb = _BRANCH_REFS.search(ls)
            if mb:
                for b in mb.group(1).split(","):
                    edges[name].append((b.strip().lstrip("%"), 1.0))
    # iterative relaxation: each computation's count is the sum over its
    # call sites of (caller count x per-call trip factor); DAG converges
    in_edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for src, outs in edges.items():
        for dst, k in outs:
            in_edges[dst].append((src, k))
    mult = {entry: 1.0}
    for _ in range(len(comps) + 2):
        changed = False
        for name in comps:
            if name == entry:
                continue
            total = 0.0
            for src, k in in_edges.get(name, ()):
                total += mult.get(src, 0.0) * k
            if total != mult.get(name, 0.0):
                mult[name] = total
                changed = True
        if not changed:
            break
    return mult


def _shape_table(lines: list[str]) -> dict[str, list[int]]:
    table = {}
    for ls in lines:
        m = _DEF_RE.match(ls)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        sm = _SHAPE_RE.match(rhs)
        if sm:
            table[name] = _dims(sm.group(2))
    return table


def analyze(text: str) -> dict:
    """Returns {'flops': total dot flops, 'collective': {...}, 'mult': ...}.
    Values are per-device (the module is the per-device SPMD program)."""
    comps = parse_computations(text)
    entry = _entry_name(text)
    if entry is None:
        return {"flops": 0.0, "collective": {"wire_bytes": 0.0}}
    mult = _multipliers(comps, entry)

    total_flops = 0.0
    per_op_bytes: dict[str, float] = defaultdict(float)
    per_op_count: dict[str, float] = defaultdict(float)

    for name, lines in comps.items():
        k = mult.get(name, 0.0)
        if k <= 0:
            continue
        table = _shape_table(lines)
        for ls in lines:
            m = _DEF_RE.match(ls)
            if not m:
                continue
            rhs = m.group(2)
            dm = _DOT_RE.match(rhs)
            if dm:
                out_dims = _dims(dm.group(2))
                lhs_name = dm.group(3)
                cdims = _dims(dm.group(5))
                lhs_shape = table.get(lhs_name)
                if lhs_shape is None:
                    # operand defined as a computation parameter; parse its
                    # shape from the dot line is impossible — skip contracted
                    # size (rare: parameters feeding dot directly)
                    contracted = 1
                else:
                    contracted = 1
                    for c in cdims:
                        if c < len(lhs_shape):
                            contracted *= lhs_shape[c]
                out_n = 1
                for d in out_dims:
                    out_n *= d
                total_flops += k * 2.0 * out_n * contracted
                continue
            for op in COLL_OPS:
                if f" {op}(" not in rhs and not rhs.startswith(f"{op}("):
                    continue
                if "-start(" in rhs or f"{op}-done" in rhs:
                    continue
                shapes = _TUPLE_SHAPES.findall(rhs.split(f"{op}(")[0])
                out = sum(
                    int_bytes(dt, ds) for dt, ds in shapes
                    if dt in DTYPE_BYTES)
                if out == 0:
                    continue
                n = _group_size(ls)
                if op == "all-gather":
                    wire = out * (n - 1) / n
                elif op == "all-reduce":
                    wire = 2 * out * (n - 1) / n
                elif op == "reduce-scatter":
                    wire = out * (n - 1)
                elif op == "all-to-all":
                    wire = out * (n - 1) / n
                else:
                    wire = out
                per_op_bytes[op] += k * wire
                per_op_count[op] += k
                break

    return {
        "flops": total_flops,
        "collective": {
            "wire_bytes": float(sum(per_op_bytes.values())),
            "per_op_bytes": dict(per_op_bytes),
            "counts": dict(per_op_count),
        },
    }


def int_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


_OPNAME_RE = re.compile(r'op_name="([^"]+)"')


def attribute_collectives(text: str, top: int = 12) -> list[tuple[float, str, str]]:
    """Wire bytes per (collective op, jax op_name) source — the dry-run's
    'profiler view' used by the §Perf hypothesis loop."""
    comps = parse_computations(text)
    entry = _entry_name(text)
    mult = _multipliers(comps, entry)
    agg: dict[tuple[str, str], float] = defaultdict(float)
    for name, lines in comps.items():
        k = mult.get(name, 0.0)
        if k <= 0:
            continue
        for ls in lines:
            for op in COLL_OPS:
                if f" {op}(" not in ls or "-start(" in ls or f"{op}-done" in ls:
                    continue
                m = _OPNAME_RE.search(ls)
                opname = re.sub(r"\d+", "N", m.group(1))[:110] if m else "?"
                lhs = ls.split(f" {op}(")[0]
                if "=" in lhs:
                    lhs = lhs.split("=", 1)[1]
                out = sum(int_bytes(dt, ds) for dt, ds in
                          _TUPLE_SHAPES.findall(lhs) if dt in DTYPE_BYTES)
                agg[(op, opname)] += k * out
                break
    rows = sorted(((b, op, nm) for (op, nm), b in agg.items()), reverse=True)
    return rows[:top]
