"""Roofline-term extraction from compiled XLA artifacts.

  compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
  memory term     = HLO_bytes / HBM_bw                 (per chip)
  collective term = collective_bytes / link_bw         (per chip)

``cost_analysis()`` on a partitioned module reports per-device FLOPs/bytes.
Collective bytes are NOT in cost_analysis: we parse ``compiled.as_text()``
(post-SPMD HLO, where the collectives exist) and sum the result-shape bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
**weighted by loop trip counts** — the layer scan wraps per-layer collectives in
a `while`, so a naive flat sum undercounts by n_layers. Trip counts are
recovered from the `constant(N)` in each while's condition computation
(heuristic, exact for lax.scan/fori_loop lowerings).
"""
from __future__ import annotations

import dataclasses
import re

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peak rates of one accelerator kind."""

    flops_bf16: float     # FLOP/s, bf16
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity, bytes
    ici_bw: float         # interconnect bytes/s per link


# Keyed by jax's ``Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect (50 GB/s on each of four links).
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               hbm_bytes=16e9, ici_bw=50e9),
}
# Off the TPU (interpret mode, compiles for a described chip) costs are
# rehearsed against this row.
REHEARSAL_KIND = "TPU v5 lite"


def device_peaks() -> DevicePeaks:
    """Peaks of the attached TPU, or the v5e rehearsal row off the TPU. A TPU
    whose kind is not in :data:`DEVICE_PEAKS` raises ``KeyError``."""
    if jax.default_backend() != "tpu":
        return DEVICE_PEAKS[REHEARSAL_KIND]
    kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}; known: "
                       f"{sorted(DEVICE_PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)(?: \([^)]*\))? \([^)]*\)\s*->", re.M)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    total_bytes: int
    by_kind: dict[str, int]
    n_ops: int


@dataclasses.dataclass
class HloCosts:
    """Trip-aware FLOPs / bytes: XLA's cost_analysis counts a while body ONCE
    regardless of trip count, so scanned-layer models under-report by ~n_layers.
    This walker multiplies per-computation costs by loop trip counts (same
    machinery as the collective counter)."""
    flops: float
    bytes_accessed: float


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """computation name -> its instruction lines."""
    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if not line.startswith((" ", "\t")) and ("->" in line) and ("{" in line):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", stripped)
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
            continue
        if cur is not None and stripped and stripped != "}":
            comps[cur].append(stripped)
        if stripped == "}":
            cur = None
    return comps


def _find_entry(hlo: str) -> str | None:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo, re.M)
    return m.group(1) if m else None


def _trip_count(comps: dict[str, list[str]], cond_comp: str) -> int:
    """Trip count of a lax.scan/fori while: resolve the constant operand of
    the condition's compare instruction (falling back to the max small
    constant in the condition)."""
    lines = comps.get(cond_comp, ())
    consts: dict[str, int] = {}
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%([\w\.\-]+) = \S+ constant\((\d+)\)", line)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for line in lines:
        if " compare(" in line:
            ops = re.findall(r"%([\w\.\-]+)", line.split("compare(", 1)[1])
            for o in ops[:2]:
                if o in consts:
                    return max(1, consts[o])
    small = [v for v in consts.values() if v <= 1 << 20]
    return max(small) if small else 1


def collective_bytes(hlo: str) -> CollectiveStats:
    comps = _split_computations(hlo)
    entry = _find_entry(hlo)

    # while instruction: condition=%c, body=%b
    while_re = re.compile(
        r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
    call_re = re.compile(r"(?:call|fusion)\(.*?\)(?:.*?)(?:to_apply|calls)=%?([\w\.\-]+)")
    cond_re = re.compile(r"conditional\(")
    branch_re = re.compile(r"(?:branch_computations=\{([^}]*)\}|"
                           r"true_computation=%?([\w\.\-]+), false_computation=%?([\w\.\-]+))")
    const_re = re.compile(r"constant\((\d+)\)")

    def trip_count(cond_comp: str) -> int:
        return _trip_count(comps, cond_comp)

    by_kind: dict[str, int] = {k: 0 for k in COLLECTIVES}
    n_ops = 0

    # "%name = SHAPE op(args...)" — SHAPE may be a tuple "(f32[..], ...)"
    inst_re = re.compile(r"=\s*(\([^)]*\)|\S+)\s+([\w\-\.]+)\(")

    def walk(comp: str, mult: int, seen: tuple = ()) -> int:
        nonlocal n_ops
        if comp in seen:   # defensive: HLO computations are acyclic
            return 0
        total = 0
        for line in comps.get(comp, ()):
            m = inst_re.search(line)
            if m:
                shape_text, op = m.group(1), m.group(2)
                kind = next((k for k in COLLECTIVES if op.startswith(k)), None)
                # async pairs (-start/-done) would double count; skip -done
                if kind and not op.startswith(kind + "-done"):
                    b = _shape_bytes(shape_text) * mult
                    by_kind[kind] += b
                    total += b
                    n_ops += mult
            m = while_re.search(line)
            if m:
                cond, bodyc = m.group(1), m.group(2)
                t = trip_count(cond)
                total += walk(bodyc, mult * t, seen + (comp,))
                continue
            m = branch_re.search(line)
            if m:
                branches = ([s.strip().lstrip("%") for s in m.group(1).split(",")]
                            if m.group(1) else [m.group(2), m.group(3)])
                # conditional: count the max-cost branch (scan/cond lowering)
                total += max((walk(b, mult, seen + (comp,)) for b in branches),
                             default=0)
                continue
            m = call_re.search(line)
            if m and any(k in line for k in ("call(",)):
                total += walk(m.group(1), mult, seen + (comp,))
        return total

    total = walk(entry, 1) if entry else 0
    return CollectiveStats(total_bytes=total, by_kind=by_kind, n_ops=n_ops)


_DEF_RE = re.compile(r"^(?:ROOT )?%([\w\.\-]+) = ((?:\([^)]*\)|\S+)) ([\w\-\.]+)\(")
_PARAM_HDR_RE = re.compile(r"%?([\w\.\-]+):\s*((?:\([^)]*\)|[\w\[\],\{\}]+))")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def hlo_costs(hlo: str) -> HloCosts:
    """Trip-aware per-device FLOPs and HBM bytes from post-SPMD HLO.

    FLOPs: every ``dot`` costs 2 * prod(output) * prod(contracting dims of the
    lhs); convolutions and elementwise ops are ignored (dots dominate).
    Bytes: every non-trivial instruction reads its array operands and writes
    its output once (fusions are walked into, so their internals do not
    double-count; the fusion's own operands/outputs are skipped then)."""
    comps = _split_computations(hlo)
    entry = _find_entry(hlo)
    while_re = re.compile(
        r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
    call_re = re.compile(r"(?:to_apply|calls|body)=%?([\w\.\-]+)")
    branch_re = re.compile(r"(?:branch_computations=\{([^}]*)\}|"
                           r"true_computation=%?([\w\.\-]+), false_computation=%?([\w\.\-]+))")
    const_re = re.compile(r"constant\((\d+)\)")

    # symbol tables: computation -> var name -> shape text
    tables: dict[str, dict[str, str]] = {}
    hdr_re = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^=]*\))?\s*\((.*)\)\s*->", )
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if not line.startswith((" ", "\t")) and "->" in line and "{" in line:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", stripped)
            cur = m.group(1) if m else None
            if cur is not None:
                tables[cur] = {}
                # header params: "name: shape, name: shape"
                inner = stripped[stripped.find("(") + 1:stripped.rfind(") ->")]
                for pm in _PARAM_HDR_RE.finditer(inner):
                    tables[cur][pm.group(1)] = pm.group(2)
            continue
        if cur is None or not stripped or stripped == "}":
            if stripped == "}":
                cur = None
            continue
        dm = _DEF_RE.match(stripped)
        if dm:
            tables[cur][dm.group(1)] = dm.group(2)

    def _dims(shape_text: str) -> list[int]:
        m = _SHAPE_RE.search(shape_text)
        if not m or not m.group(2):
            return []
        return [int(d) for d in m.group(2).split(",") if d]

    def trip_count(cond_comp: str) -> int:
        return _trip_count(comps, cond_comp)

    def walk(comp: str, mult: float, seen: tuple = ()) -> tuple[float, float]:
        if comp in seen:
            return 0.0, 0.0
        fl = by = 0.0
        table = tables.get(comp, {})
        for line in comps.get(comp, ()):
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            out_shape, op = dm.group(2), dm.group(3)
            wm = while_re.search(line)
            if wm:
                t = trip_count(wm.group(1))
                f2, b2 = walk(wm.group(2), mult * t, seen + (comp,))
                fl, by = fl + f2, by + b2
                continue
            bm = branch_re.search(line)
            if bm and "conditional(" in line:
                branches = ([s.strip().lstrip("%") for s in bm.group(1).split(",")]
                            if bm.group(1) else [bm.group(2), bm.group(3)])
                subs = [walk(b, mult, seen + (comp,)) for b in branches]
                if subs:
                    f2, b2 = max(subs)
                    fl, by = fl + f2, by + b2
                continue
            if op == "fusion":
                cm = call_re.search(line)
                if cm:
                    f2, b2 = walk(cm.group(1), mult, seen + (comp,))
                    fl += f2
                # fusion IO bytes: operands + output
                ob = _shape_bytes(out_shape)
                args = line[line.find("fusion(") + 7:line.find(")", line.find("fusion("))]
                ib = sum(_shape_bytes(table.get(a, "")) for a in
                         _OPERAND_RE.findall(args))
                by += (ob + ib) * mult
                continue
            if op.startswith("dot"):
                args = line[line.find("(") + 1:]
                names = _OPERAND_RE.findall(args)[:1]
                lhs_shape = table.get(names[0], "") if names else ""
                cdims = _CONTRACT_RE.search(line)
                contraction = 1
                ld = _dims(lhs_shape)
                if cdims and ld:
                    for ci in (int(x) for x in cdims.group(1).split(",") if x):
                        if ci < len(ld):
                            contraction *= ld[ci]
                out_elems = 1
                for d in _dims(out_shape):
                    out_elems *= d
                fl += 2.0 * out_elems * contraction * mult
                ob = _shape_bytes(out_shape)
                ib = sum(_shape_bytes(table.get(a, ""))
                         for a in _OPERAND_RE.findall(args)[:2])
                by += (ob + ib) * mult
                continue
            if op in ("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "after-all"):
                continue
            # generic op: output + operand bytes
            ob = _shape_bytes(out_shape)
            args = line[line.find("(") + 1:line.find(")", line.find("("))] \
                if "(" in line else ""
            ib = sum(_shape_bytes(table.get(a, ""))
                     for a in _OPERAND_RE.findall(args))
            by += (ob + ib) * mult
        return fl, by

    fl, by = walk(entry, 1.0) if entry else (0.0, 0.0)
    return HloCosts(flops=fl, bytes_accessed=by)


@dataclasses.dataclass
class Roofline:
    flops: float               # per-device
    hbm_bytes: float           # per-device
    coll_bytes: float          # per-device
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    peak_bytes: float          # per-device HBM high-water mark

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def analyze(compiled, hlo_text: str | None = None) -> Roofline:
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    # trip-aware costs (XLA's cost_analysis counts while bodies once;
    # scanned-layer programs under-report by ~n_layers without this)
    costs = hlo_costs(text)
    flops = costs.flops
    hbm = costs.bytes_accessed
    peaks = device_peaks()
    tc = flops / peaks.flops_bf16
    tm = hbm / peaks.hbm_bw
    tx = coll.total_bytes / peaks.ici_bw
    terms = {"compute": tc, "memory": tm, "collective": tx}
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=float(coll.total_bytes),
                    t_compute=tc, t_memory=tm, t_collective=tx,
                    bottleneck=max(terms, key=terms.get), peak_bytes=float(peak))
