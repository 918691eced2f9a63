"""Device time per generation by program scope, from a profiler trace of a
cell's problems; not part of a run.

    python3 bench/phases.py --workload table1.de_chunked --seed 7

The program names its work (``src/repro/core/obs.py``): every op a phase
lowers to carries the phase's ``popt.*`` scope in its ``op_name`` metadata,
and the engine writes ``popt.*`` host spans on the profiler's clock. This
reduction reads both. A TPU trace's op events carry no ``op_name``; the
trace holds it all the same, in the HLO proto of each program it ran (the
``/host:metadata`` plane), and each op's event metadata names its program
(``program_id``). Each leaf device op is put down to the innermost
``popt.*`` component of its ``op_name`` (``unscoped`` where there is none);
the generations in the window are the runs of the ``while`` that sits
directly under ``popt.round`` (the scan over a round's generations) times
``sync_every``; each idle gap is put down to the innermost program span
(``popt.*``, or ``bench.*`` other than the window span) that covers its
midpoint, never to another thread's Python-tracer event.

The command solves one problem to warm the programs, one untraced and one
traced (:func:`trace_problem`, the window where the cell's configuration
puts it), and prints one JSON line: both problems' wall times, the
per-scope reduction and, on the same trace, what ``tracing.reduce`` and the
accepted readers ``device_idle_share.solve`` and
``gen_roofline_share.solve`` read. Off the TPU it prints nothing and exits
2.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import threading
import time
from typing import Iterator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, peaks, tracing  # noqa: E402

UNSCOPED = "unscoped"
NO_SPAN = "no program span"
SCOPE = re.compile(r"popt\.[a-z_.]+")
# the op_name tail of the scan over a round's generations ("vmap(popt.round)"
# where rounds of a jobs axis run under vmap)
ROUND_LOOP = re.compile(r"popt\.round\)*/while$")
PHASES = {"variation_us": "popt.variation", "eval_us": "popt.evaluate",
          "retry_eval_us": "popt.retry", "select_us": "popt.select"}


@dataclasses.dataclass(frozen=True)
class Op(tracing.Event):
    """A device op with its ``op_name`` metadata (``path``) and the innermost
    ``popt.*`` component of it (``scope``)."""

    path: str = ""
    scope: str = UNSCOPED


def innermost(path: str) -> str:
    """The innermost scope of an ``op_name``: ``jit(run)/popt.round/while/
    body/vmap(popt.variation)/add`` -> ``popt.variation``."""
    found = SCOPE.findall(path)
    return found[-1] if found else UNSCOPED


def _varint(b, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b) -> Iterator[tuple[int, int | memoryview]]:
    """``(field number, value)`` of one protobuf message, in wire order: an
    int for a varint, the bytes of anything else."""
    b, i = memoryview(b), 0
    while i < len(b):
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, v


def _repeated(b, field: int) -> list:
    return [v for f, v in _fields(b) if f == field]


def _first(b, field: int, default=b""):
    return next((v for f, v in _fields(b) if f == field), default)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _hlo_op_names(hlo_proto) -> dict[str, str]:
    """``{instruction: op_name}`` of an ``HloProto``: ``hlo_module`` (1) ->
    ``computations`` (3) -> ``instructions`` (2) -> ``name`` (1) and
    ``metadata`` (7) -> ``op_name`` (2)."""
    out = {}
    for comp in _repeated(_first(hlo_proto, 1), 3):
        for inst in _repeated(comp, 2):
            out[_text(_first(inst, 1))] = _text(_first(_first(inst, 7), 2))
    return out


def op_names(path: str) -> dict[str, str]:
    """``{device op event name: op_name}`` from an ``.xplane.pb``'s own
    records. ``XSpace.planes`` (1); a plane's ``name`` (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5), both maps of entries
    ``key`` (1) / ``value`` (2); a metadata's ``name`` (2) and ``stats`` (5);
    a stat's ``metadata_id`` (1) and value (3, 4: ints; 5, 6: strings). The
    ``/host:metadata`` plane holds one event metadata per program, named
    ``jit_run(<program_id>)``, with its ``Hlo Proto``; a device plane's op
    metadata is named as its op events are and carries ``program_id``. A name
    two programs give ops of different ``op_name`` maps to ``""``."""
    with open(path, "rb") as fh:
        space = fh.read()
    programs: dict[int, dict[str, str]] = {}
    ops: dict[str, list[tuple[int, str]]] = collections.defaultdict(list)
    for plane in _repeated(space, 1):
        name = _text(_first(plane, 2))
        device = tracing.is_device_plane(name)
        if not device and name != "/host:metadata":
            continue
        stat_name = {_first(e, 1, 0): _text(_first(_first(e, 2), 2))
                     for e in _repeated(plane, 5)}
        for entry in _repeated(plane, 4):
            md = _first(entry, 2)
            md_name = _text(_first(md, 2))
            stats = {stat_name.get(_first(st, 1, 0)): st
                     for st in _repeated(md, 5)}
            if not device and "Hlo Proto" in stats:
                pid = int(md_name.rsplit("(", 1)[1].rstrip(")"))
                st = stats["Hlo Proto"]
                programs[pid] = _hlo_op_names(_first(st, 6) or _first(st, 5))
            elif device and "program_id" in stats:
                st = stats["program_id"]
                ops[md_name].append((_first(st, 3, None) or _first(st, 4, 0),
                                     tracing.op_name(md_name)))
    out: dict[str, str] = {}
    for text, where in ops.items():
        found = {programs.get(pid, {}).get(inst, "") for pid, inst in where}
        out[text] = found.pop() if len(found) == 1 else ""
    return out


def load(path: str) -> tuple[list[tracing.Event], collections.Counter]:
    """``tracing.load``'s events, each device op as an :class:`Op` with its
    ``op_name`` (:func:`op_names`); and how many device ops were found in a
    program of the trace (``found``) or not (``missing``)."""
    from jax.profiler import ProfileData
    names = op_names(path)
    scope_of = {p: innermost(p) for p in {*names.values(), ""}}
    out: list[tracing.Event] = []
    used: collections.Counter = collections.Counter()
    for pl in ProfileData.from_file(path).planes:
        dev = tracing.is_device_plane(pl.name)
        if not dev and not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            if dev and ln.name != "XLA Ops":
                continue
            for e in ln.events:
                t0, dt = float(e.start_ns), float(e.duration_ns)
                if not dev:
                    out.append(tracing.Event(pl.name, ln.name, e.name, t0, dt))
                    continue
                p = names.get(e.name)
                used["found" if p is not None else "missing"] += 1
                out.append(Op(pl.name, ln.name, e.name, t0, dt, p or "",
                              scope_of[p or ""]))
    return out, used


def _by_device(events: list[tracing.Event]) -> dict[str, list[tracing.Event]]:
    by: dict[str, list[tracing.Event]] = collections.defaultdict(list)
    for e in events:
        if tracing.is_device_plane(e.plane):
            by[e.plane].append(e)
    return by


def scopes(events: list[tracing.Event], lo: float, hi: float) -> dict:
    """``{scope: [leaf-op seconds, leaf ops]}`` inside ``[lo, hi)``, mean over
    devices. An op cut by an edge counts by its time inside; it counts as an
    op where it starts inside."""
    by = _by_device(events)
    acc: dict[str, list[float]] = collections.defaultdict(lambda: [0.0, 0.0])
    for ops in by.values():
        for e in tracing.leaves(ops):
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            row = acc[getattr(e, "scope", UNSCOPED)]
            if b > a:
                row[0] += (b - a) / 1e9
            if lo <= e.start_ns < hi:
                row[1] += 1
    n = max(1, len(by))
    return {k: [v[0] / n, v[1] / n] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1][0])}


def is_round_loop(e: tracing.Event) -> bool:
    """The scan over one round's generations: the ``while`` directly under
    ``popt.round``."""
    return (e.dur_ns > 0 and ROUND_LOOP.search(getattr(e, "path", ""))
            is not None and tracing.op_kind(e.name) == "while")


def round_loop_runs(events: list[tracing.Event], lo: float, hi: float) -> float:
    """Runs of the round's generation scan inside ``[lo, hi)``, mean over
    devices; a run cut by an edge counts by its share inside."""
    by = _by_device(events)
    runs = 0.0
    for ops in by.values():
        for e in filter(is_round_loop, ops):
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                runs += (b - a) / e.dur_ns
    return runs / max(1, len(by))


def whole_rounds(ops: list[tracing.Event], lo: float, hi: float
                 ) -> tuple[float, float, int]:
    """``(start, end, runs)`` of one device's round scans that lie wholly in
    ``[lo, hi]``: from the first one's start to the last one's end. A TPU
    trace drops an op that began before the profiler started, so a long
    scan cut by the window's first edge is missing rather than cut."""
    runs = sorted((e.start_ns, e.end_ns) for e in ops
                  if is_round_loop(e) and lo <= e.start_ns and e.end_ns <= hi)
    if not runs:
        return lo, lo, 0
    return runs[0][0], runs[-1][1], len(runs)


def loop_runs(ops: list[tracing.Event], lo: float, hi: float) -> dict:
    """``{while op: runs}`` of one device's loops inside ``[lo, hi)``, a run
    cut by an edge by its share inside (as ``tracing.reduce`` counts)."""
    out: collections.Counter = collections.Counter()
    for e in ops:
        if e.dur_ns > 0 and tracing.op_kind(e.name) == "while":
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                out[tracing.op_name(e.name)] += (b - a) / e.dur_ns
    return dict(out.most_common())


def busy_and_gaps(events: list[tracing.Event], lo: float, hi: float
                  ) -> tuple[float, list[tuple[float, float]]]:
    """Busy seconds inside ``[lo, hi)`` (the union of leaf ops, mean over
    devices, as ``tracing.reduce`` counts it) and every device's gaps."""
    by = _by_device(events)
    busy, gaps = 0.0, []
    for ops in by.values():
        iv = tracing.clip(tracing.union(
            [(e.start_ns, e.end_ns) for e in tracing.leaves(ops)]), lo, hi)
        busy += sum(b - a for a, b in iv) / 1e9
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return busy / max(1, len(by)), gaps


def is_program_span(e: tracing.Event) -> bool:
    return (not tracing.is_device_plane(e.plane) and e.name != tracing.WINDOW_SPAN
            and e.name.startswith(("popt.", "bench.")))


def idle_by_program_span(events: list[tracing.Event],
                         gaps: list[tuple[float, float]], n_dev: int,
                         top: int = 10) -> list:
    """``tracing.idle_by_span`` over the program's and the driver's own spans
    alone: a gap no such span covers is put down to ``no program span``."""
    spans = [e for e in events if is_program_span(e)]
    out = tracing.idle_by_span(spans, gaps, n_dev, top)
    return [[NO_SPAN if k == "no host span" else k, v] for k, v in out]


def per_generation(ops: list[tracing.Event], sync_every: int, lo: float,
                   hi: float) -> dict | None:
    """One device's four phases, the rest of its busy time, its leaf-op sum
    and op count, per generation, over its whole round scans in ``[lo,
    hi]`` (``None`` where it has none); and the loops' runs there, whose
    most-run loop in chunked DE is the chunk loop, once per generation."""
    a, b, runs = whole_rounds(ops, lo, hi)
    if not runs:
        return None
    gens = runs * sync_every
    sc = scopes(ops, a, b)
    busy, _ = busy_and_gaps(ops, a, b)
    per = {k: 1e6 * sc.get(s, [0.0, 0.0])[0] / gens for k, s in PHASES.items()}
    per["rest_us"] = 1e6 * busy / gens - sum(per.values())
    per["busy_us"] = 1e6 * busy / gens
    per["leaf_sum_us"] = 1e6 * sum(v[0] for v in sc.values()) / gens
    per["ops_per_gen"] = sum(v[1] for v in sc.values()) / gens
    per["by_scope_us"] = {k: 1e6 * v[0] / gens for k, v in sc.items()}
    per["generations"] = gens
    per["span_s"] = (b - a) / 1e9
    per["loop_runs"] = loop_runs(ops, a, b)
    return per


def reduce(events: list[tracing.Event], sync_every: int) -> dict:
    """The per-scope reduction of a trace: over its window, busy time, the
    generations (the round scans' runs times ``sync_every``), each scope's
    leaf-op seconds and op count and the idle gaps by program span; over the
    whole round scans of each device, the numbers per generation
    (:func:`per_generation`, one entry per device)."""
    lo, hi = tracing.window_of(events)
    by = _by_device(events)
    busy_s, gaps = busy_and_gaps(events, lo, hi)
    per = [per_generation(ops, sync_every, lo, hi) for ops in by.values()]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s, "devices": len(by),
            "generations": round_loop_runs(events, lo, hi) * sync_every,
            "scopes": scopes(events, lo, hi),
            "idle_gaps": idle_by_program_span(events, gaps, max(1, len(by))),
            "per_generation": [p for p in per if p is not None]}


def trace_problem(root: str, solve, seed: int, offset_s: float,
                  length_s: float, margin_s: float = 0.05) -> tuple[str, float]:
    """Solve one problem while a thread traces it: the window
    (``tracing.WINDOW_SPAN``, ``length_s`` long, from ``offset_s`` into the
    problem) inside a profiler session that opens ``margin_s`` before it and
    closes ``margin_s`` after it. A TPU trace keeps only the ops and spans
    that begin and end inside the session, so a loop that straddles an edge
    of the window is in the trace only where the session reaches past that
    edge by more than the loop's run. Returns the trace file and the
    problem's wall time."""
    import jax
    trace_dir = os.path.join(root, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    errors: list[BaseException] = []

    def capture() -> None:
        try:
            time.sleep(max(0.0, offset_s - margin_s))
            jax.profiler.start_trace(trace_dir)
            try:
                time.sleep(margin_s)
                with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                    time.sleep(length_s)
                time.sleep(margin_s)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    thread = threading.Thread(target=capture, name="bench-trace")
    t0 = time.perf_counter()
    thread.start()
    try:
        solve(seed)
    finally:
        wall = time.perf_counter() - t0
        thread.join()
    if errors:
        raise errors[0]
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    return files[0], wall


def probe(root: str, workload: str, seed: int, require_chip: bool = True,
          bench: dict | None = None) -> dict:
    """Warm the cell's problem, solve it once untraced and once traced, and
    reduce the trace (see the module's docstring)."""
    cell = harness.Cell(root, workload, bench)
    info, _ = harness.open_chip(root, cell, require_chip)
    cfg, driver = cell.config, cell.driver()
    solve = driver.solver(cfg)
    solve(seed)                                         # warm every program
    t0 = time.perf_counter()
    solve(seed + 1)
    untraced = time.perf_counter() - t0
    try:
        path, traced = trace_problem(root, solve, seed + 2,
                                     float(cfg["trace_offset_s"]),
                                     float(cfg["trace_seconds"]))
        events, used = load(path)
    finally:
        shutil.rmtree(os.path.join(root, ".bench_trace"), ignore_errors=True)
    req = driver.request(cfg)
    base = tracing.reduce(events)
    rec = {"driver": cfg["driver"], "trace": base,
           "peak": peaks.peaks(info["kind"]) if require_chip else None,
           "solve": {"fn": req["fn"], "pop": req["pop"], "dim": req["dim"],
                     "islands": req.get("n_islands", 1),
                     "chunked": req.get("algo") == "de" and req.get(
                         "params", {}).get("barrier_mode") == "chunked"}}
    roof = harness.load_module(
        os.path.join(root, "bench", "metrics", "gen_roofline_share.solve.py"),
        "bench_phases_gen_roofline")
    return {
        "device": info,
        "problem_wall_s": {"untraced": untraced, "traced": traced},
        "op_names": dict(used),
        "phases": reduce(events, int(req["sync_every"])),
        "accepted": {m: cell.reader(m)(rec) for m in
                     ("device_idle_share.solve", "gen_roofline_share.solve")},
        "chunk_loop_generations": roof.generations(rec),
        "tracing_reduce": {k: base[k] for k in ("window_s", "busy_s", "devices",
                                                "device_ops", "idle_gaps")},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        out = probe(ROOT, args.workload, args.seed)
    except harness.NoChip as e:
        print(f"phases: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
