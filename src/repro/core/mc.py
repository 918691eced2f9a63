"""MCS — parallel pure Monte-Carlo random search (popt4jlib.MonteCarlo).

The paper's benchmark baseline: draw uniformly from the box, keep the best.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.islands import MetaHeuristic, State, uniform_init
from repro.functions.benchmarks import Function

Array = jax.Array


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
) -> MetaHeuristic:
    """Pure Monte-Carlo sampling policy — the paper's MCS baseline."""
    lo, hi = f.lo, f.hi

    def init(key: Array) -> State:
        x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {"pop": x, "fit": fit, "best_arg": x[i], "best_val": fit[i]}

    def gen(state: State, key: Array) -> State:
        with obs.scope(obs.VARIATION):
            x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        with obs.scope(obs.SELECT):
            i = jnp.argmin(fit)
            better = fit[i] < state["best_val"]
            return {
                "pop": x, "fit": fit,
                "best_val": jnp.where(better, fit[i], state["best_val"]),
                "best_arg": jnp.where(better, x[i], state["best_arg"]),
            }

    return MetaHeuristic("mc", init, gen, evals_per_gen=pop, init_evals=pop)
