"""Trip-count-aware cost models for the roofline analysis.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE (verified in
tests/test_costs.py), so any scan-over-layers model is undercounted by the
layer count.  Two correctors:

1. ``jaxpr_cost(fn, *args)`` — walks the closed jaxpr multiplying scan bodies
   by their trip counts: dot_general FLOPs exactly, a semantic HBM-traffic
   model (dot operands/outputs per use, gather/scatter moved bytes,
   elementwise assumed fused).  Numbers are GLOBAL (pre-partitioning):
   divide by chip count for the ideal per-device cost.  Remat recompute is
   included because grad-of-checkpoint jaxprs contain the recompute eqns.

2. ``collectives_with_trip_counts(hlo_text)`` — per-computation collective
   byte sums from the post-SPMD HLO, multiplied through the while-loop call
   chain (trip count recovered from the loop-condition constant).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any

import jax
import numpy as np


# ------------------------------------------------------------ jaxpr walk --

def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dnums
    batch = int(np.prod([lhs.shape[i] for i in lb])) if lb else 1
    contract = int(np.prod([lhs.shape[i] for i in lc])) if lc else 1
    m = int(np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                     if i not in lc and i not in lb]))
    n = int(np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                     if i not in rc and i not in rb]))
    return 2 * batch * m * n * contract


_CALL_PRIMS = ("pjit", "closed_call", "core_call", "remat_call", "remat2",
               "checkpoint", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "custom_jvp_call_jaxpr")

_GATHERLIKE = ("gather", "take", "dynamic_slice", "take_along_axis")
_SCATTERLIKE = ("scatter", "scatter-add", "scatter_add", "scatter_apply",
                "dynamic_update_slice")


def _jaxpr_of(params: dict):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "body_jaxpr"):
        if key in params:
            j = params[key]
            return getattr(j, "jaxpr", j)
    return None


def _walk(jaxpr, acc: dict, mult: int) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            f = _dot_flops(eqn) * mult
            acc["flops"] += f
            b = (sum(_aval_bytes(v.aval) for v in eqn.invars)
                 + sum(_aval_bytes(v.aval) for v in eqn.outvars)) * mult
            acc["bytes"] += b
            acc["dot_flops"] += f
        elif name == "scan":
            inner = _jaxpr_of(eqn.params)
            length = eqn.params.get("length", 1)
            _walk(inner, acc, mult * int(length))
        elif name == "while":
            inner = _jaxpr_of(eqn.params)
            if inner is not None:
                acc["unbounded_while"] += 1
                _walk(inner, acc, mult)
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            if branches:
                subaccs = []
                for br in branches:
                    sub = _new_acc()
                    _walk(getattr(br, "jaxpr", br), sub, mult)
                    subaccs.append(sub)
                worst = max(subaccs, key=lambda a: a["flops"] + a["bytes"])
                for k in worst:
                    acc[k] += worst[k]
        elif name in _CALL_PRIMS:
            inner = _jaxpr_of(eqn.params)
            if inner is not None:
                _walk(inner, acc, mult)
        elif any(g in name for g in _GATHERLIKE):
            acc["bytes"] += sum(_aval_bytes(v.aval)
                                for v in eqn.outvars) * mult
            acc["gather_bytes"] += sum(_aval_bytes(v.aval)
                                       for v in eqn.outvars) * mult
        elif any(s in name for s in _SCATTERLIKE):
            upd = (_aval_bytes(eqn.invars[-1].aval)
                   if eqn.invars else 0)
            acc["bytes"] += upd * mult
        else:
            # elementwise / reductions: ~1 flop per output element, bytes
            # assumed fused away (post-fusion HBM model)
            out_elems = 0
            for v in eqn.outvars:
                try:
                    out_elems += int(np.prod(v.aval.shape))
                except Exception:
                    pass
            acc["ew_flops"] += out_elems * mult
            acc["flops"] += out_elems * mult


def _new_acc() -> dict:
    return defaultdict(int)


def jaxpr_cost(fn, *args, **kw) -> dict:
    """Global trip-count-aware cost of ``fn(*args)``. Returns a dict with
    flops, bytes (semantic HBM model), dot_flops, ew_flops, gather_bytes."""
    closed = jax.make_jaxpr(fn, **kw)(*args)
    acc = _new_acc()
    _walk(closed.jaxpr, acc, 1)
    return dict(acc)


# ----------------------------------------------- HLO collective parsing ---

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\)?,?\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls|condition|body|branch_computations)="
                      r"%?([\w.\-{}, ]+)")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w!]+\[[^\]]*\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(text: str) -> dict:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in text.splitlines():
        stripped = line.strip()
        m = _COMP_RE.match(line) if "{" in line and "->" in line else None
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
        elif stripped == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(stripped)
    return comps


def collectives_with_trip_counts(text: str) -> dict:
    """Collective bytes from post-SPMD HLO, scaled by while trip counts."""
    comps = _split_computations(text)

    # trip count of a while = largest s32 constant in its condition comp
    def trip_of(cond_name: str) -> int:
        consts = []
        for line in comps.get(cond_name, []):
            consts += [int(c) for c in _CONST_RE.findall(line)]
        return max(consts) if consts else 1

    # edges: parent comp -> (child comp, multiplier)
    children: dict[str, list[tuple[str, int]]] = defaultdict(list)
    entry = None
    for name, lines in comps.items():
        if entry is None or name.startswith("main") or ".main" in name:
            pass
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.groups()
                t = trip_of(cond)
                children[name].append((body, t))
                children[name].append((cond, t))
            else:
                for cm in re.finditer(
                        r"(?:to_apply|calls)=%?([\w.\-]+)", line):
                    children[name].append((cm.group(1), 1))

    # entry = computation that is not anyone's child
    child_names = {c for kids in children.values() for c, _ in kids}
    roots = [n for n in comps if n not in child_names]

    mult: dict[str, int] = defaultdict(int)
    def propagate(name, m):
        if mult[name] >= m and mult[name] > 0:
            return
        mult[name] = max(mult[name], m)
        for child, k in children.get(name, []):
            propagate(child, m * k)
    for r in roots:
        propagate(r, 1)

    by_op: dict[str, float] = defaultdict(float)
    by_group: dict[str, float] = defaultdict(float)
    raw = 0
    n = 0
    for name, lines in comps.items():
        m = max(1, mult.get(name, 1))
        for line in lines:
            cm = _COLL_RE.search(line)
            if not cm:
                continue
            nbytes = _shape_bytes(cm.group(1))
            raw += nbytes
            by_op[cm.group(2)] += nbytes * m
            gm = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
            if gm:
                gsize = len(gm.group(1).split(","))
            else:
                gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
                gsize = int(gm2.group(2)) if gm2 else 0
            by_group[f"group{gsize}"] += nbytes * m
            n += 1
    return {"bytes_by_op": dict(by_op),
            "bytes_by_group_size": dict(by_group),
            "n_collectives": n,
            "total_bytes": sum(by_op.values()),
            "raw_once_counted_bytes": raw}
