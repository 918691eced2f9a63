"""DGABH — island-model Generalized Adaptive Basin Hopping (popt4jlib.BH, after [2]).

Each walker: perturb (ChromosomePerturberIntf -> Gaussian kick), descend with a
short stochastic local search (shrinking-step (1+1) probes), then Metropolis-accept
the new basin. Islands exchange walkers through the engine's starvation/ring
policies exactly like DGA.
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
    n_ls: int = 5,              # local-search probes per hop
    perturb_frac: float = 0.25, # basin-hop kick size
    ls_frac: float = 0.05,      # local-search initial step
    ls_shrink: float = 0.6,
    T: float = 1.0,             # Metropolis temperature between basins
) -> MetaHeuristic:
    """Basin-Hopping per-island policy (kick + local probe + Metropolis)."""
    lo, hi = f.lo, f.hi
    kick = perturb_frac * (hi - lo)
    step0 = ls_frac * (hi - lo)

    def init(key: Array) -> State:
        x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {"pop": x, "fit": fit, "best_arg": x[i], "best_val": fit[i]}

    def local_search(y: Array, fy: Array, key: Array):
        def body(c, carry):
            y, fy = carry
            with obs.scope(obs.VARIATION):
                k = jax.random.fold_in(key, c)
                step = step0 * (ls_shrink ** c)
                y2 = clip_box(y + step * jax.random.normal(k, y.shape), lo, hi)
            fy2 = evaluator(y2)
            with obs.scope(obs.SELECT):
                imp = fy2 < fy
                return jnp.where(imp[:, None], y2, y), jnp.where(imp, fy2, fy)

        return jax.lax.fori_loop(0, n_ls, body, (y, fy))

    def gen(state: State, key: Array) -> State:
        x, fx = state["pop"], state["fit"]
        with obs.scope(obs.VARIATION):
            kk, kl, ka = jax.random.split(key, 3)
            y = clip_box(x + kick * jax.random.normal(kk, x.shape), lo, hi)
        fy = evaluator(y)
        y, fy = local_search(y, fy, kl)
        with obs.scope(obs.SELECT):
            dF = fy - fx
            accept = ((dF <= 0)
                      | (jax.random.uniform(ka, fx.shape) < jnp.exp(-dF / T)))
            x = jnp.where(accept[:, None], y, x)
            fx = jnp.where(accept, fy, fx)
            i = jnp.argmin(fx)
            better = fx[i] < state["best_val"]
            return {
                "pop": x, "fit": fx,
                "best_val": jnp.where(better, fx[i], state["best_val"]),
                "best_arg": jnp.where(better, x[i], state["best_arg"]),
            }

    return MetaHeuristic("bh", init, gen,
                         evals_per_gen=pop * (1 + n_ls), init_evals=pop)
