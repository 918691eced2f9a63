"""Profiler traces: capture a stretch of the measured window, and reduce the
trace to device busy time, idle gaps, loop runs and the longest ops.

The reduction works on plain records, ``Event(plane, line, name, start_ns,
dur_ns)``, so the tests can feed it a small synthetic trace. A device op that
contains other ops on its line (a ``while`` or ``conditional`` around its
body) is not counted on its own: busy time is the union of the leaf ops.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import shutil
import threading
import time

import numpy as np

WINDOW_SPAN = "bench.traced_window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") or name.startswith("/device:GPU:")


def load(path: str) -> list[Event]:
    """Events of an ``.xplane.pb``: every op on the devices' ``XLA Ops`` lines
    and every host event."""
    from jax.profiler import ProfileData
    out: list[Event] = []
    for pl in ProfileData.from_file(path).planes:
        dev = is_device_plane(pl.name)
        if not dev and not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            if dev and ln.name != "XLA Ops":
                continue
            for e in ln.events:
                out.append(Event(pl.name, ln.name, e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_kind(hlo_text: str) -> str:
    """The HLO opcode of an op event: ``collective-permute-done``, ``fusion``."""
    if " = " not in hlo_text:
        return op_name(hlo_text).rstrip("0123456789.")
    rhs = hlo_text.split(" = ", 1)[1]
    # skip the result shape, which may be a tuple "(f32[..], s32[])"
    depth, i = 0, 0
    while i < len(rhs):
        ch = rhs[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
        i += 1
    rest = rhs[i:].strip()
    return rest.split("(", 1)[0].strip()


def leaves(ops: list[Event]) -> list[Event]:
    """Ops that contain no other op of their line."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start_ns < e.end_ns and nxt.end_ns <= e.end_ns \
                and nxt.line == e.line:
            continue
        out.append(e)
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(iv: list[tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def window_of(events: list[Event]) -> tuple[float, float]:
    """The traced window: the host span the harness wrote around it."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return spans[0].start_ns, spans[0].end_ns


def reduce(events: list[Event], top: int = 10) -> dict:
    """Busy and idle time per device, the runs of each loop, the longest ops
    and the longest idle gaps by the host span they fall in, over the traced
    window.

    Returns ``{"window_s", "busy_s" (mean over devices), "devices",
    "device_ops", "idle_gaps", "loops"}``: the ops and gaps as ``[[name,
    seconds], ...]``, longest first; ``loops`` as ``[[name, runs, HLO text],
    ...]``, most runs first, where ``runs`` counts the executions of a
    ``while`` op in the window (mean over devices), one cut by an edge of the
    window by its share inside."""
    lo, hi = window_of(events)
    window_s = (hi - lo) / 1e9
    by_dev: dict[str, list[Event]] = collections.defaultdict(list)
    for e in events:
        if is_device_plane(e.plane):
            by_dev[e.plane].append(e)
    host = [e for e in events if not is_device_plane(e.plane)
            and e.name != WINDOW_SPAN]
    busy = []
    op_time: collections.Counter = collections.Counter()
    loops: collections.Counter = collections.Counter()
    loop_text: dict[str, str] = {}
    gaps: list[tuple[float, float]] = []
    for plane, ops in sorted(by_dev.items()):
        leaf = leaves(ops)
        iv = clip(union([(e.start_ns, e.end_ns) for e in leaf]), lo, hi)
        busy.append(sum(b - a for a, b in iv) / 1e9)
        for e in leaf:
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                op_time[op_name(e.name)] += (b - a) / 1e9
        for e in ops:
            if op_kind(e.name) == "while" and e.dur_ns > 0:
                a, b = max(e.start_ns, lo), min(e.end_ns, hi)
                if b > a:
                    loops[op_name(e.name)] += (b - a) / e.dur_ns
                    loop_text.setdefault(op_name(e.name), e.name[:600])
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n_dev = max(1, len(by_dev))
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "devices": len(by_dev),
        "device_ops": [[k, v] for k, v in op_time.most_common(top)],
        "idle_gaps": idle_by_span(host, gaps, n_dev, top),
        "loops": [[k, v / n_dev, loop_text[k]] for k, v in loops.most_common()],
    }


def idle_by_span(host: list[Event], gaps: list[tuple[float, float]],
                 n_dev: int, top: int, attribute: int = 2000) -> list:
    """Idle seconds (mean over devices) by the innermost host event that
    covers each gap's midpoint; the longest ``attribute`` gaps are attributed
    one by one, the rest are summed as short gaps."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    starts = np.asarray([e.start_ns for e in host], np.float64)
    ends = np.asarray([e.end_ns for e in host], np.float64)
    durs = ends - starts
    out: collections.Counter = collections.Counter()
    for a, b in gaps[:attribute]:
        t = (a + b) / 2
        cover = np.flatnonzero((starts <= t) & (ends >= t))
        name = (host[cover[np.argmin(durs[cover])]].name[:80] if cover.size
                else "no host span")
        out[name] += (b - a) / 1e9 / n_dev
    rest = gaps[attribute:]
    if rest:
        longest = (rest[0][1] - rest[0][0]) / 1e3
        short = sum(b - a for a, b in rest) / 1e9 / n_dev
        out[f"gaps of {longest:.1f} us or less"] += short
    return [[k, v] for k, v in out.most_common(top)]


class Capture:
    """Trace ``length_s`` of the window, starting ``offset_s`` after
    :meth:`start`, on a thread of its own so the window's work goes on. The
    trace directory lives in the checkout and is removed once reduced."""

    def __init__(self, root: str, offset_s: float, length_s: float) -> None:
        self.dir = os.path.join(root, ".bench_trace")
        self.offset_s, self.length_s = offset_s, length_s
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)

        def body() -> None:
            try:
                time.sleep(self.offset_s)
                jax.profiler.start_trace(self.dir)
                try:
                    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                        time.sleep(self.length_s)
                finally:
                    jax.profiler.stop_trace()
            except BaseException as e:  # noqa: BLE001 — re-raised by result()
                self.error = e

        self._thread = threading.Thread(target=body, name="bench-trace")
        self._thread.start()

    def result(self) -> dict:
        """Wait for the capture, reduce it, delete the files."""
        assert self._thread is not None
        self._thread.join()
        if self.error is not None:
            raise self.error
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return reduce(load(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
