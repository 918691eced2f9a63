"""DFA — island-model Firefly Algorithm (popt4jlib.PS.FA, after Yang [7]).

Fig.4 setup: beta=1, delta=0.97 (randomness decay), gamma=200, L=1/sqrt(gamma).
Every firefly moves toward each brighter one with attraction beta*exp(-gamma r^2)
plus a decaying random walk; O(P^2 D) per generation (P is small: 50 in the paper).

Eval accounting: the pairwise attraction reads only the *cached* fitness of
the previous generation — none of the O(P^2) interactions queries the
objective — so a generation consumes exactly ``pop`` evaluations (one batch
evaluator call on the moved swarm) for ANY population size, not just the
paper's P=50 default. ``evals_per_gen=pop`` below is that invariant, and
``tests/test_metaheuristics.py::test_evals_per_gen_parity`` counts actual
evaluator rows at a non-default ``pop`` to enforce it for all eight
registered policies.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.islands import MetaHeuristic, State, clip_box, uniform_init
from repro.functions.benchmarks import Function

Array = jax.Array


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
    beta0: float = 1.0,
    gamma: float = 200.0,
    delta: float = 0.97,
    alpha0: float = 1.0,
) -> MetaHeuristic:
    """Firefly Algorithm per-island policy (attraction beta0, absorption gamma)."""
    lo, hi = f.lo, f.hi
    L = 1.0 / jnp.sqrt(gamma)

    def init(key: Array) -> State:
        x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {
            "pop": x, "fit": fit, "alpha": jnp.asarray(alpha0, jnp.float32),
            "best_arg": x[i], "best_val": fit[i],
        }

    def gen(state: State, key: Array) -> State:
        x, fit, alpha = state["pop"], state["fit"], state["alpha"]
        with obs.scope(obs.VARIATION):
            diff = x[None, :, :] - x[:, None, :]        # (i, j, D): x_j - x_i
            r2 = jnp.sum(diff * diff, axis=-1)          # (i, j)
            brighter = (fit[None, :] < fit[:, None]).astype(x.dtype)
            attract = beta0 * jnp.exp(-gamma * r2) * brighter
            move = jnp.einsum("ij,ijd->id", attract, diff)
            noise = alpha * L * (jax.random.uniform(key, x.shape) - 0.5)
            x = clip_box(x + move + noise, lo, hi)
        fit = evaluator(x)   # the generation's ONLY objective queries: P rows
        with obs.scope(obs.SELECT):
            i = jnp.argmin(fit)
            better = fit[i] < state["best_val"]
            return {
                "pop": x, "fit": fit, "alpha": alpha * delta,
                "best_val": jnp.where(better, fit[i], state["best_val"]),
                "best_arg": jnp.where(better, x[i], state["best_arg"]),
            }

    return MetaHeuristic("fa", init, gen, evals_per_gen=pop, init_evals=pop)
