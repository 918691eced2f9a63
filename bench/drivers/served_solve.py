"""Problems solved back to back through the optimization service, each with
its own seed drawn from the run's.

The configuration's ``"request"`` is one ``OptRequest`` (every field but the
seed). Each problem goes to an in-process ``OptimizationService``
(``launch/opt_serve.py``) with the ``submit`` op and comes back from
``scheduler.result(id, evict=True)``, the call the ``result`` op makes, whose
``OptimizeResult`` carries the history the check needs. The service's bucket
is full with one job (``max_batch=1``) and runs in the submitting thread
(``workers=0``), so a submit dispatches its bucket at once and no flush
deadline is waited on. A request with ``devices > 1`` runs the scheduler's
sharded bucket: ``minimize_many`` under ``shard_map``.

Set-up solves one problem, so that every program is compiled before the
window. The window solves problems until ``--seconds`` have passed and runs
to the end of the one in flight, so ``evals_per_s`` counts all the work over
all the time. A traced run also puts the device time down to the program's
scopes (:class:`ScopeCapture`).
"""
from __future__ import annotations

import glob
import os
import shutil
import threading
import time

import numpy as np

from bench import phases, tracing, traffic
from bench.check import Answer
from bench.drivers.solve import request


class ScopeCapture(tracing.Capture):
    """``tracing.Capture`` whose result also holds, from the same trace, the
    device seconds under each innermost ``popt.*`` scope (``"scopes"``, mean
    over the chips) and the generations run in the window
    (``"generations"``), both as ``bench/phases.py`` computes them. As there,
    the profiler session opens ``margin_s`` before the window and closes
    ``margin_s`` after it, so that a round's generation scan cut by an edge
    of the window is in the trace and counts by its share inside; a round
    lasts a few milliseconds, and every op in the margins is read too, so
    the margins are short."""

    def __init__(self, root: str, offset_s: float, length_s: float,
                 sync_every: int, margin_s: float = 0.01) -> None:
        super().__init__(root, offset_s, length_s)
        self.sync_every, self.margin_s = sync_every, margin_s

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)

        def body() -> None:
            try:
                time.sleep(max(0.0, self.offset_s - self.margin_s))
                jax.profiler.start_trace(self.dir)
                try:
                    time.sleep(self.margin_s)
                    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                        time.sleep(self.length_s)
                    time.sleep(self.margin_s)
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
            events, _ = phases.load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = tracing.reduce(events)
        lo, hi = tracing.window_of(events)
        out["scopes"] = {k: v[0] for k, v in phases.scopes(events, lo, hi).items()}
        out["generations"] = (phases.round_loop_runs(events, lo, hi)
                              * self.sync_every)
        return out


def solver(cfg: dict):
    """``solve(seed) -> Answer`` for the configuration's request, through one
    in-process service."""
    from repro.launch.opt_serve import OptimizationService
    fields = request(cfg)
    service = OptimizationService(max_batch=1, workers=0)

    def solve(seed: int) -> Answer:
        reply = service.handle({"op": "submit",
                                "request": dict(fields, seed=seed)})
        if "error" in reply:
            return Answer(fields, seed, f"error: {reply['error']}")
        resp = service.scheduler.result(reply["id"], evict=True)
        r = resp.result
        if resp.status != "done" or r is None:
            return Answer(fields, seed, f"{resp.status}: {resp.error}")
        return Answer(fields, seed, "done", float(r.value), np.asarray(r.arg),
                      int(r.n_evals), np.asarray(r.history))

    return solve


def run(ctx: dict) -> dict:
    import jax
    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    cfg = cell.config
    req = request(cfg)
    g = traffic.rng(seed)
    solve = solver(cfg)
    log = []
    t0 = time.perf_counter()
    warm = solve(int(g.integers(0, 2**31 - 1)))
    log.append(f"set-up problem: {warm.status} in "
               f"{time.perf_counter() - t0!r} s")
    setup_s = time.perf_counter() - ctx["t_start"]
    cap = None
    if ctx["trace"]:
        cap = ScopeCapture(ctx["root"], float(cfg["trace_offset_s"]),
                           float(cfg["trace_seconds"]), int(req["sync_every"]))
    answers, walls = [], []
    t0 = time.perf_counter()
    if cap is not None:
        cap.start()
    while True:
        s = int(g.integers(0, 2**31 - 1))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.served_solve.problem"):
            answers.append(solve(s))
        walls.append(time.perf_counter() - t1)
        if time.perf_counter() - t0 >= seconds:
            break
    window = time.perf_counter() - t0
    trace = cap.result() if cap is not None else None
    done = [a for a in answers if a.status == "done"]
    log.append(f"window: {len(answers)} problems in {window!r} s, each "
               f"{min(walls)!r} to {max(walls)!r} s")
    if trace is not None:
        log.append(f"trace generations: {trace['generations']!r}")
        log += [f"trace scope {k}: {v!r} s" for k, v in trace["scopes"].items()]
    evals = sum(a.n_evals for a in done)
    return {
        "answers": answers, "attempted": len(answers),
        "failed": len(answers) - len(done), "log": log, "trace": trace,
        "end_to_end": {"evals_per_s": evals / window, "setup_s": setup_s},
        "record": {"served_solve": {
            "fn": req["fn"], "pop": req["pop"], "dim": req["dim"],
            "islands": req.get("n_islands", 1)}},
    }
