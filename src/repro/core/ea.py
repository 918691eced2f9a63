"""DEA — multi-threaded Evolutionary Algorithm (popt4jlib.EA, after Michalewicz [4]).

A (mu + lambda) evolution strategy with Gaussian mutation and a multiplicative
1/5th-success-rule step-size adaptation — the classical EA the paper benchmarks.
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
    lam: int | None = None,
    sigma0_frac: float = 0.3,
) -> MetaHeuristic:
    """(mu+lambda) Evolutionary Algorithm per-island policy."""
    lo, hi = f.lo, f.hi
    lam = lam if lam is not None else pop

    def init(key: Array) -> State:
        x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {
            "pop": x, "fit": fit,
            "sigma": jnp.asarray(sigma0_frac * (hi - lo), jnp.float32),
            "best_arg": x[i], "best_val": fit[i],
        }

    def gen(state: State, key: Array) -> State:
        x, fit, sigma = state["pop"], state["fit"], state["sigma"]
        with obs.scope(obs.VARIATION):
            kp, km = jax.random.split(key)
            parents = jax.random.randint(kp, (lam,), 0, pop)
            child = clip_box(
                x[parents] + sigma * jax.random.normal(km, (lam, dim)), lo, hi)
        cfit = evaluator(child)

        with obs.scope(obs.SELECT):
            # (mu + lambda) selection
            allx = jnp.concatenate([x, child], axis=0)
            allf = jnp.concatenate([fit, cfit], axis=0)
            keep = jnp.argsort(allf)[:pop]
            x, fit = allx[keep], allf[keep]

            # 1/5th success rule on the offspring
            succ = jnp.mean((cfit < jnp.median(fit)).astype(jnp.float32))
            sigma = jnp.clip(sigma * jnp.where(succ > 0.2, 1.05, 0.95),
                             1e-8 * (hi - lo), (hi - lo))
            i = jnp.argmin(fit)
            better = fit[i] < state["best_val"]
            return {
                "pop": x, "fit": fit, "sigma": sigma,
                "best_val": jnp.where(better, fit[i], state["best_val"]),
                "best_arg": jnp.where(better, x[i], state["best_arg"]),
            }

    return MetaHeuristic("ea", init, gen, evals_per_gen=lam, init_evals=pop)
