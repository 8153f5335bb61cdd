"""Post-SPMD HLO analysis with while-loop trip-count rollup.

``compiled.cost_analysis()`` famously counts each while body ONCE — a
scan-over-layers train step under-reports FLOPs by ~n_layers x n_micro.
XLA records ``backend_config={"known_trip_count":{"n":...}}`` on while ops
it has bounded, so we parse the HLO text into computations, then roll up

  * matmul FLOPs      — every ``dot`` op: 2 x numel(result) x K,
  * collective bytes  — result bytes of all-gather / all-reduce /
                        reduce-scatter / all-to-all / collective-permute
                        (sync or -start async form),

multiplying through nested loop trip counts.  This is the honest per-device
profile the roofline terms are derived from.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

_DTYPE_BYTES = {"f64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16, "s64": 8, "u64": 8, "f8e4m3fn": 1,
                "f8e5m2": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\)\s*->\s*.+\s*\{")
_WHILE_RE = re.compile(r"\bwhile\(")
_BODY_RE = re.compile(r"body=(%[\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*(\d+)')
_DOT_RE = re.compile(r"\bdot\(")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")


def _numel_bytes(type_str: str) -> tuple[int, int]:
    """(numel, bytes) of the FIRST shape in a type string (tuples summed)."""
    total_n = total_b = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        total_n += n
        total_b += n * _DTYPE_BYTES[dt]
    return total_n, total_b


@dataclasses.dataclass
class Computation:
    name: str
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0
    coll: dict[str, dict[str, float]] = dataclasses.field(default_factory=dict)
    whiles: list[tuple[str, int]] = dataclasses.field(default_factory=list)
    calls: list[str] = dataclasses.field(default_factory=list)


# ops whose operands/results do NOT represent HBM traffic
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "while", "conditional", "call", "after-all", "token",
             "opt-barrier", "partition-id", "replica-id", "iota"}


def parse_hlo(txt: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    shapes: dict[str, str] = {}          # %name -> type str (per computation)

    for line in txt.splitlines():
        mc = _COMP_RE.match(line.strip())
        if mc and line.rstrip().endswith("{"):
            name = mc.group(1).lstrip("%")
            cur = Computation(name)
            comps[name] = cur
            shapes = {}
            if line.strip().startswith("ENTRY"):
                entry = name
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        iname, rest = mi.groups()
        # record result type for operand-shape lookups
        tm = _SHAPE_RE.search(rest)
        if tm:
            shapes[iname] = rest[:rest.find(" ", tm.end())] \
                if " " in rest[tm.end():] else rest

        # -- while ---------------------------------------------------------
        if _WHILE_RE.search(rest):
            bm = _BODY_RE.search(rest)
            tm2 = _TRIP_RE.search(rest)
            trip = int(tm2.group(1)) if tm2 else 1
            if bm:
                cur.whiles.append((bm.group(1).lstrip("%"), trip))
            continue

        # -- call / fusion-with-computation / conditional --------------------
        for cm in re.finditer(r"(?:to_apply|called_computations|"
                              r"true_computation|false_computation|"
                              r"branch_computations)=\{?(%[\w.\-]+)", rest):
            pass    # reductions etc — negligible flops, skip

        if re.search(r"=\s*\S+\s+call\(", rest) or " fusion(" in rest:
            km = re.search(r"(?:to_apply|calls)=(%[\w.\-]+)", rest)
            if km:
                cur.calls.append(km.group(1).lstrip("%"))

        # -- collectives -----------------------------------------------------
        # rest looks like:  bf16[36,64]{1,0} all-gather(%p), channel_id=...
        opm = re.match(r"(\([^)]*\)|\S+)\s+([\w\-]+)\(", rest)
        opname = opm.group(2) if opm else ""
        base_op = opname.removesuffix("-start")
        if base_op in COLLECTIVES and not opname.endswith("-done"):
            head = opm.group(1)
            _, nbytes = _numel_bytes(head)
            if opname.endswith("-start"):
                nbytes //= 2              # async tuple repeats the buffer
            slot = cur.coll.setdefault(base_op, {"count": 0, "bytes": 0.0})
            slot["count"] += 1
            slot["bytes"] += float(nbytes)
            continue

        # -- HBM traffic proxy -------------------------------------------------
        # post-fusion, each materialized op reads its operands and writes its
        # result once: traffic ~= result bytes + operand bytes (shape-table
        # lookup).  Free/structural ops are skipped.  This is the loop-
        # adjusted replacement for cost_analysis' "bytes accessed".
        if opm and opname not in _FREE_OPS and not opname.endswith("-done"):
            _, rbytes = _numel_bytes(opm.group(1))
            traffic = float(rbytes)
            om2 = re.search(rf"{re.escape(opname)}\(([^)]*)\)", rest)
            if om2:
                for operand in om2.group(1).split(","):
                    operand = operand.strip()
                    if operand.startswith("%") and operand in shapes:
                        _, ob = _numel_bytes(shapes[operand])
                        traffic += float(ob)
            cur.hbm_bytes += traffic

        # -- dots ------------------------------------------------------------
        if opname == "dot":
            res_head = rest.split("dot(")[0]
            res_n, _ = _numel_bytes(res_head)
            cm = _CONTRACT_RE.search(rest)
            k = 1
            opm = re.search(r"dot\(([^)]*)\)", rest)
            if cm and opm:
                lhs_name = opm.group(1).split(",")[0].strip()
                lhs_type = shapes.get(lhs_name, "")
                sm = _SHAPE_RE.search(lhs_type)
                if sm:
                    dims = [int(x) for x in sm.group(2).split(",") if x]
                    for ci in cm.group(1).split(","):
                        if ci and int(ci) < len(dims):
                            k *= dims[int(ci)]
            cur.dot_flops += 2.0 * res_n * k

    return comps, entry


def rollup(comps: dict[str, Computation], entry: str) -> dict[str, Any]:
    """Total dot FLOPs + collective bytes of the entry, loop-multiplied."""
    memo: dict[str, tuple[float, dict]] = {}

    def visit(name: str) -> tuple[float, float, dict[str, dict[str, float]]]:
        if name in memo:
            return memo[name]
        c = comps.get(name)
        if c is None:
            return 0.0, 0.0, {}
        flops = c.dot_flops
        hbm = c.hbm_bytes
        coll: dict[str, dict[str, float]] = {
            k: dict(v) for k, v in c.coll.items()}
        for callee in c.calls:
            f2, b2, c2 = visit(callee)
            flops += f2
            hbm += b2
            _merge(coll, c2, 1)
        for body, trip in c.whiles:
            f2, b2, c2 = visit(body)
            flops += trip * f2
            hbm += trip * b2
            _merge(coll, c2, trip)
        memo[name] = (flops, hbm, coll)
        return memo[name]

    flops, hbm, coll = visit(entry)
    return {"dot_flops": flops, "hbm_bytes": hbm, "collectives": coll,
            "collective_bytes": sum(v["bytes"] for v in coll.values())}


def _merge(dst, src, mult):
    for op, v in src.items():
        slot = dst.setdefault(op, {"count": 0, "bytes": 0.0})
        slot["count"] += v["count"] * mult
        slot["bytes"] += v["bytes"] * mult


def analyze(hlo_text: str) -> dict[str, Any]:
    comps, entry = parse_hlo(hlo_text)
    out = rollup(comps, entry)
    out["n_computations"] = len(comps)
    return out
