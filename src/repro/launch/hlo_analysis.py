"""Post-SPMD HLO analysis for the roofline.

XLA's ``cost_analysis()`` (and any naive text scan) counts while-loop bodies
ONCE, but every layer stack here is a lax.scan — so flops/bytes/collectives
would be undercounted by ~n_layers. This module parses the optimized HLO
into computations, extracts each while op's ``known_trip_count`` from its
backend_config, walks the call graph with multiplicities, and accumulates
per-device:

  * dot_flops        — 2*M*N*K per dot, the MXU work (elementwise flops are
                       <2% for these models and are reported separately via
                       cost_analysis for reference);
  * hbm_bytes        — Σ over surviving (post-fusion) instructions of
                       operand+result bytes, the same definition
                       HloCostAnalysis uses;
  * collective bytes — operand sizes of all-gather / all-reduce /
                       reduce-scatter / all-to-all / collective-permute.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_info(shape_text: str) -> tuple[int, list[list[int]]]:
    """(total bytes, list of dims lists) over every dtype[dims] occurrence."""
    total = 0
    dims_all = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in DTYPE_BYTES:
            continue
        dd = [int(d) for d in dims.split(",") if d]
        n = 1
        for d in dd:
            n *= d
        total += n * DTYPE_BYTES[dtype]
        dims_all.append(dd)
    return total, dims_all


@dataclass
class Computation:
    name: str
    bytes_accessed: int = 0
    bytes_fused: int = 0
    dot_flops: int = 0
    coll_bytes: dict = field(default_factory=lambda: defaultdict(int))
    coll_counts: dict = field(default_factory=lambda: defaultdict(int))
    # (callee, multiplicity) edges: while bodies get their trip count
    calls: list = field(default_factory=list)


_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_CALLSITE = re.compile(
    r"(?:body=|condition=|to_apply=|calls=|branch_computations=\{)\s*%?([\w\.\-]+)"
)
_TRIP = re.compile(r"known_trip_count[^0-9]*(\d+)")
_OPC = re.compile(r"^\s*((?:\([^)]*\))|(?:[\w\[\],{}\s]+?))\s+([\w\-]+)(?:\.\d+)?\(")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "copy-start", "copy-done", "after-all", "partition-id", "replica-id",
}

# Ops that genuinely stream HBM on TPU even under aggressive fusion. CPU XLA
# fuses far less than TPU, so counting EVERY instruction's operands+results
# ("raw") wildly overstates TPU HBM traffic from elementwise chains; the
# "fused" estimate counts only these anchor ops (their operands/results are
# the fusion boundaries: weights, activations entering/leaving matmuls,
# caches, gathers/scatters, big reductions, data movement between loop
# iterations).
_HBM_ANCHOR_OPS = {
    "dot", "convolution", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "reduce", "reduce-window", "sort", "rng",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "select-and-scatter", "cholesky",
    "triangular-solve", "fft", "custom-call", "pad", "concatenate",
    "slice", "reverse", "transpose", "broadcast-to",
}


def parse_hlo(text: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    entry = None
    cur: Computation | None = None
    shapes: dict[str, str] = {}
    pending: list[tuple] = []

    for raw in text.splitlines():
        m = _COMP_HEADER.match(raw.strip()) if not raw.startswith(" ") else None
        if m:
            cur = Computation(m.group(2))
            comps[cur.name] = cur
            if m.group(1):
                entry = cur.name
            continue
        if cur is None:
            continue
        mi = _INSTR.match(raw)
        if not mi:
            continue
        name, rhs = mi.group(1), mi.group(2)
        # result shape + opcode
        mo = _OPC.match(rhs)
        if not mo:
            continue
        shape_text, opcode = mo.group(1).strip(), mo.group(2)
        shapes[name] = shape_text
        pending.append((cur.name, name, shape_text, opcode, rhs))

    # second pass with the full shape table
    for comp_name, name, shape_text, opcode, rhs in pending:
        comp = comps[comp_name]
        result_bytes, result_dims = _shape_info(shape_text)

        # call edges
        if opcode in ("while",):
            trip = 1
            mt = _TRIP.search(rhs)
            if mt:
                trip = int(mt.group(1))
            for callee in _CALLSITE.findall(rhs):
                comp.calls.append((callee, trip))
        elif opcode in ("call", "fusion", "conditional", "custom-call", "reduce",
                        "map", "sort", "scatter", "select-and-scatter",
                        "reduce-window", "async-start"):
            for callee in _CALLSITE.findall(rhs):
                comp.calls.append((callee, 1))

        # operand bytes
        paren = rhs[rhs.index("(") :] if "(" in rhs else "()"
        depth = 0
        end = 0
        for i, ch in enumerate(paren):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        args_text = paren[1:end]
        operand_bytes = 0
        for ref in re.findall(r"%([\w\.\-]+)", args_text):
            if ref in shapes:
                operand_bytes += _shape_info(shapes[ref])[0]

        # slicing/indexing ops touch only the sliced region, not the full
        # operand buffer (dynamic-update-slice writes in place: the update
        # region, not the carry buffer)
        if opcode in ("dynamic-slice", "slice", "gather"):
            touched = 2 * result_bytes
        elif opcode in ("dynamic-update-slice", "scatter"):
            # in-place update: read update (+indices) and write that region
            touched = 2 * max(operand_bytes - result_bytes, 0)
        else:
            touched = result_bytes + operand_bytes
        if opcode not in _SKIP_BYTES_OPS and opcode != "while":
            comp.bytes_accessed += touched
            if opcode in _HBM_ANCHOR_OPS:
                comp.bytes_fused += touched

        if opcode == "dot":
            # contraction sizes from lhs shape + contracting dims
            md = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
            refs = re.findall(r"%([\w\.\-]+)", args_text)
            if md and refs and refs[0] in shapes:
                _, lhs_dims_list = _shape_info(shapes[refs[0]])
                if lhs_dims_list:
                    lhs_dims = lhs_dims_list[0]
                    k = 1
                    for ci in md.group(1).split(","):
                        if ci:
                            k *= lhs_dims[int(ci)]
                    out_elems = 1
                    for dd in result_dims:
                        for d in dd:
                            out_elems *= d
                    comp.dot_flops += 2 * out_elems * k

        for coll in COLLECTIVES:
            if opcode.startswith(coll):
                comp.coll_bytes[coll] += operand_bytes or result_bytes
                comp.coll_counts[coll] += 1
                break

    return comps, entry or next(iter(comps), "")


def _accumulate(comps: dict[str, Computation], entry: str) -> dict:
    """DFS with loop multiplicities (memoized per (comp))."""
    totals = {"bytes": 0, "bytes_fused": 0, "dot_flops": 0,
              "coll": defaultdict(int), "coll_counts": defaultdict(int)}

    import sys
    sys.setrecursionlimit(10000)

    cache: dict[str, dict] = {}

    def visit(name: str) -> dict:
        if name in cache:
            return cache[name]
        comp = comps.get(name)
        if comp is None:
            return {"bytes": 0, "bytes_fused": 0, "dot_flops": 0, "coll": {},
                    "coll_counts": {}}
        out = {
            "bytes": comp.bytes_accessed,
            "bytes_fused": comp.bytes_fused,
            "dot_flops": comp.dot_flops,
            "coll": dict(comp.coll_bytes),
            "coll_counts": dict(comp.coll_counts),
        }
        for callee, mult in comp.calls:
            sub = visit(callee)
            out["bytes"] += mult * sub["bytes"]
            out["bytes_fused"] += mult * sub["bytes_fused"]
            out["dot_flops"] += mult * sub["dot_flops"]
            for k, v in sub["coll"].items():
                out["coll"][k] = out["coll"].get(k, 0) + mult * v
            for k, v in sub["coll_counts"].items():
                out["coll_counts"][k] = out["coll_counts"].get(k, 0) + mult * v
        cache[name] = out
        return out

    return visit(entry)


def analyze_hlo(text: str) -> dict:
    """Loop-aware per-device totals from optimized HLO text."""
    comps, entry = parse_hlo(text)
    tot = _accumulate(comps, entry)
    coll_total = sum(tot["coll"].values())
    return {
        "dot_flops": int(tot["dot_flops"]),
        "hbm_bytes": int(tot["bytes_fused"]),
        "hbm_bytes_raw": int(tot["bytes"]),
        "collective_bytes": int(coll_total),
        "collectives": {k: int(v) for k, v in tot["coll"].items()},
        "collective_counts": {k: int(v) for k, v in tot["coll_counts"].items()},
        "n_computations": len(comps),
    }


def collective_bytes(hlo_text: str) -> dict:
    """Back-compat wrapper: loop-aware collective accounting."""
    a = analyze_hlo(hlo_text)
    out = dict(a["collectives"])
    out["total"] = a["collective_bytes"]
    out["counts"] = a["collective_counts"]
    return out


# ---------------------------------------------------------------------------
# roofline terms: published per-chip peaks, one row per device kind
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s of one chip-to-chip interconnect link
    source: str


# Keyed by ``jax.Device.device_kind``. A kind that is not here is an error,
# never a default: a roofline priced with another chip's peaks is wrong.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        # 1,600 Gbit/s per chip over its 4 links: 50 GB/s per link, the
        # unit the collective term prices
        ici_bw=1600e9 / 8 / 4,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}

# the device kind the dry runs and the kernel and roofline benches price at
MODELED_CHIP = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}"
        ) from None


def roofline_terms(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    coll_bytes_per_device: float,
    n_chips: int,
    peaks: ChipPeaks,
) -> dict:
    """Three-term roofline over PER-DEVICE quantities (the SPMD-partitioned
    module is the per-device program, so chips appear via the partitioned
    shapes, not an extra division), priced at ``peaks``."""
    compute_s = hlo_flops / peaks.flops_bf16
    memory_s = hlo_bytes / peaks.hbm_bw
    collective_s = coll_bytes_per_device / peaks.ici_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant
    terms["bound_s"] = terms[dominant]
    return terms
