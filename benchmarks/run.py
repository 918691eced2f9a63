"""Benchmark harness: one entry per paper artifact. Prints
``name,us_per_call,derived`` CSV rows.

  table1_de_gen      §V.A DDE generation step (shifted Rosenbrock-1000, pop 800)
  fig4_lite          §V.B pairwise subset (5 methods x 5 functions, reduced dim)
  executor_eval      distributed-evaluator throughput per backend (xla/pallas)
  fused_de_island    device-resident DDE: XLA step vs fused de_step kernel
  de_kernel_parity   fused de_step kernel vs XLA reference (correctness +
                     relative call time; Pallas runs interpreted on CPU)
  roofline_summary   per-cell dominant terms from the saved dry-run artifacts

Full-budget reproductions: benchmarks/table1_de_scaling.py and
benchmarks/fig4_pairwise.py (see EXPERIMENTS.md).
"""
from __future__ import annotations

import glob
import json
import sys
import time

import jax
import jax.numpy as jnp


def _t(fn, n=3):
    fn()  # compile
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n * 1e6


def table1_de_gen() -> None:
    from repro.core import ALGORITHMS, IslandConfig, IslandOptimizer
    from repro.functions import make_shifted_rosenbrock
    f = make_shifted_rosenbrock(1000)
    cfg = IslandConfig(n_islands=1, pop=800, dim=1000, migration="none",
                       sync_every=10, max_evals=800 * 50)
    opt = IslandOptimizer(ALGORITHMS["de"], cfg,
                          params={"w": 0.5, "px": 0.2, "barrier_mode": "chunked"})
    t0 = time.time()
    res = opt.minimize(f, jax.random.PRNGKey(0))
    wall = time.time() - t0
    per_gen = wall / max(res.n_gens, 1) * 1e6
    print(f"table1_de_gen,{per_gen:.1f},best={res.value:.1f}")


def fig4_lite() -> None:
    from benchmarks.fig4_pairwise import run_method
    methods = ["sa", "ga", "de", "mc", "fcg"]
    fns = ["sphere", "rastrigin", "rosenbrock", "ackley", "lnd1"]
    t0 = time.time()
    wins = {m: 0 for m in methods}
    vals = {m: [] for m in methods}
    for fn in fns:
        for m in methods:
            vals[m].append(run_method(m, fn, 16, 8000, 0))
    for i, fn in enumerate(fns):
        best = min(methods, key=lambda m: vals[m][i])
        wins[best] += 1
    per = (time.time() - t0) / (len(methods) * len(fns)) * 1e6
    order = sorted(wins, key=lambda m: -wins[m])
    print(f"fig4_lite,{per:.0f},winner_order={'>'.join(order)}")


def executor_eval() -> None:
    """Distributed-evaluator throughput per EvalBackend (xla vs pallas)."""
    from repro.core.executor import ExecutorConfig, make_batch_evaluator
    from repro.functions import get
    pop = jax.random.uniform(jax.random.PRNGKey(0), (4096, 256),
                             minval=-5, maxval=5)
    for backend in ("xla", "pallas"):
        ev = jax.jit(make_batch_evaluator(get("rastrigin"),
                                          ExecutorConfig(backend=backend)))
        us = _t(lambda: ev(pop).block_until_ready())
        print(f"executor_eval_{backend},{us:.1f},evals_per_s={4096/us*1e6:.0f}")


def fused_de_island() -> None:
    """DDE under the device-resident engine, XLA step vs fused de_step kernel."""
    from repro.core import ALGORITHMS, IslandConfig, IslandOptimizer
    from repro.functions import get
    f = get("rastrigin")
    cfg = IslandConfig(n_islands=1, pop=256, dim=128, migration="none",
                       sync_every=10, max_evals=256 * 40)
    for fused in (False, True):
        opt = IslandOptimizer(ALGORITHMS["de"], cfg, params={"fused": fused})
        opt.minimize(f, jax.random.PRNGKey(0))        # warm the jit cache
        t0 = time.time()
        res = opt.minimize(f, jax.random.PRNGKey(0))
        per_gen = (time.time() - t0) / max(res.n_gens, 1) * 1e6
        tag = "fused" if fused else "xla"
        print(f"fused_de_island_{tag},{per_gen:.0f},best={res.value:.1f}")


def de_kernel_parity() -> None:
    from repro.kernels import ops, ref
    P, D = 256, 1000
    key = jax.random.PRNGKey(1)
    pop = jax.random.uniform(key, (P, D), minval=-100, maxval=100)
    fit = ref.bench_eval_ref(pop, "rastrigin")
    i = jnp.arange(P)
    idx = jnp.stack([(i + 3) % P, (i + 7) % P, (i + 11) % P])
    u = jax.random.uniform(jax.random.fold_in(key, 2), (P, D))
    jr = jax.random.randint(jax.random.fold_in(key, 3), (P,), 0, D)
    a1, a2 = ops.de_step(pop, fit, idx, u, jr, fn="rastrigin")
    b1, b2 = ref.de_step_ref(pop, fit, idx, u, jr, fn="rastrigin")
    err = float(jnp.max(jnp.abs(a2 - b2) / (jnp.abs(b2) + 1)))
    us = _t(lambda: ops.de_step(pop, fit, idx, u, jr, fn="rastrigin")[1]
            .block_until_ready(), n=1)
    print(f"de_kernel_parity,{us:.0f},maxrelerr={err:.2e}(interpret-mode)")


def roofline_summary() -> None:
    cells = sorted(glob.glob("experiments/dryrun/*.json"))
    n_ok = n_fit = 0
    worst = (0.0, "")
    for c in cells:
        r = json.load(open(c))
        if r.get("status") != "ok":
            continue
        n_ok += 1
        if r["memory"].get("fits_16GB_analytic"):
            n_fit += 1
        tx = r["per_device"]["t_collective"]
        if tx > worst[0]:
            worst = (tx, f"{r['arch']}/{r['shape']}")
    print(f"roofline_summary,{n_ok},fit16GB={n_fit} worst_tx={worst[1]}")


def main() -> int:
    """Run every entry; an entry that raises prints an ``ERROR`` row, the
    rest still run, and the exit code is 1 if any row errored."""
    print("name,us_per_call,derived")
    n_err = 0
    for fn in (table1_de_gen, fig4_lite, executor_eval, fused_de_island,
               de_kernel_parity, roofline_summary):
        try:
            fn()
        except Exception as e:  # keep the harness running
            n_err += 1
            print(f"{fn.__name__},nan,ERROR:{type(e).__name__}:{e}",
                  file=sys.stdout)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
