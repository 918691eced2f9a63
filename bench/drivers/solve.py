"""Problems solved back to back, each with its own seed drawn from the run's.

The configuration's ``"request"`` is one ``OptRequest`` (every field but the
seed), solved with ``IslandOptimizer.minimize`` on the engine
``core.scheduler.build_optimizer`` builds for it, as ``opt_serve`` would.
Set-up solves one problem to warm every program. The window solves problems
until ``--seconds`` have passed and runs to the end of the one in flight, so
``evals_per_s`` counts all the work over all the time.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic, tracing
from bench.check import Answer


def request(cfg: dict) -> dict:
    """The configuration's request, with ``max_evals`` for its
    ``"generations"``: every island evaluates its population at init and once
    per generation."""
    r = dict(cfg["request"])
    r["max_evals"] = r["pop"] * r.get("n_islands", 1) * (int(cfg["generations"]) + 1)
    return r


def solver(cfg: dict):
    """``solve(seed) -> Answer`` for the configuration's request."""
    import jax
    from repro.core.api import OptRequest
    from repro.core.scheduler import build_optimizer
    from repro.functions import get
    fields = request(cfg)
    req = OptRequest.from_dict(dict(fields, seed=0))
    opt, f = build_optimizer(req), get(req.fn, req.dim)

    def solve(seed: int) -> Answer:
        r = opt.minimize(f, jax.random.PRNGKey(seed))
        return Answer(fields, seed, "done", float(r.value),
                      np.asarray(r.arg), int(r.n_evals), np.asarray(r.history))

    return solve


def run(ctx: dict) -> dict:
    import jax
    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    cfg = cell.config
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
        cap = tracing.Capture(ctx["root"], float(cfg["trace_offset_s"]),
                              float(cfg["trace_seconds"]))
    answers, walls = [], []
    t0 = time.perf_counter()
    if cap is not None:
        cap.start()
    while True:
        s = int(g.integers(0, 2**31 - 1))
        t1 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.solve.problem"):
                answers.append(solve(s))
        except Exception as e:  # noqa: BLE001 — a failed problem is counted
            answers.append(Answer(request(cfg), s, f"error: {e!r}"))
        walls.append(time.perf_counter() - t1)
        if time.perf_counter() - t0 >= seconds:
            break
    window = time.perf_counter() - t0
    trace = cap.result() if cap is not None else None
    done = [a for a in answers if a.status == "done"]
    req = request(cfg)
    log.append(f"window: {len(answers)} problems in {window!r} s, each "
               f"{min(walls)!r} to {max(walls)!r} s")
    evals = sum(a.n_evals for a in done)
    gens = sum((len(a.history)) * req["sync_every"] for a in done)
    return {
        "answers": answers, "attempted": len(answers),
        "failed": len(answers) - len(done), "log": log, "trace": trace,
        "end_to_end": {"evals_per_s": evals / window, "setup_s": setup_s},
        "record": {"solve": {"gens_per_s": gens / window, "fn": req["fn"],
                             "pop": req["pop"], "dim": req["dim"],
                             "islands": req.get("n_islands", 1),
                             "chunked": req.get("algo") == "de" and req.get(
                                 "params", {}).get("barrier_mode") == "chunked"}},
    }
