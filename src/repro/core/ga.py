"""DGA — island-model Genetic Algorithm (popt4jlib.GA).

Paper features reproduced: elitist roulette-wheel selection on per-generation
fitness; 1-pt crossover + per-allele mutation (XOverOpIntf / MutationOpIntf ->
pure functions); the aging mechanism (each individual draws a Gaussian age limit
at birth and dies past it, so island populations vary over time); starvation
migration is handled by the engine via the ``alive`` mask. Fixed-capacity
population arrays + liveness masks replace Java's growing/shrinking ArrayLists
(static shapes for XLA); a dead slot carries +inf fitness and is never selected.
The island best is exempt from aging (elitism).

``fused=True`` routes the offspring wave — crossover, mutation, evaluation,
slot-placement selection — through the fused ``kernels.ga_step`` Pallas
kernel via the engine's ``step_override`` hook; aging, roulette sampling and
the worst-slot argsort stay in XLA (cross-population ops). Same key
discipline as the XLA path, so both are bit-comparable on a fixed seed.
Requires an objective registered in ``kernels.registry``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.islands import MetaHeuristic, State, clip_box, uniform_init
from repro.functions.benchmarks import Function
from repro.kernels import registry as kreg
from repro.kernels.autotune import KernelConfig
from repro.kernels.ga_step import ga_step as _ga_step_kernel

Array = jax.Array


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
    pc: float = 0.7,            # 1-pt crossover probability (Fig.4 setup)
    pm: float = 0.1,            # per-allele mutation probability (Fig.4 setup)
    mut_scale: float = 0.1,     # Gaussian mutation sigma, fraction of box width
    n_offspring: int | None = None,
    age_mean: float = 1e9,      # aging disabled by default (Fig.4 single-island runs)
    age_sd: float = 0.0,
    fused: bool = False,        # offspring wave in one Pallas kernel
    interpret: bool | None = None,
    kernel_cfg: KernelConfig | None = None,
) -> MetaHeuristic:
    """Genetic Algorithm per-island policy (1-pt crossover, Gaussian mutation,
    optional aging — the paper's DGA island member)."""
    lo, hi = f.lo, f.hi
    n_off = n_offspring if n_offspring is not None else max(1, pop // 4)
    sigma_m = mut_scale * (hi - lo)

    def draw_limits(key: Array, n: int) -> Array:
        return age_mean + age_sd * jax.random.normal(key, (n,))

    def init(key: Array) -> State:
        kp, kl = jax.random.split(key)
        p = uniform_init(kp, pop, dim, lo, hi)
        fit = evaluator(p)
        i = jnp.argmin(fit)
        return {
            "pop": p, "fit": fit,
            "age": jnp.zeros((pop,), jnp.float32),
            "age_limit": draw_limits(kl, pop).astype(jnp.float32),
            "alive": jnp.ones((pop,), bool),
            "best_arg": p[i], "best_val": fit[i],
        }

    def gen(state: State, key: Array) -> State:
        p, fit = state["pop"], state["fit"]
        with obs.scope(obs.VARIATION):
            age, limit = state["age"] + 1.0, state["age_limit"]
            alive = state["alive"]
            ksel, kcut, kco, kmm, kmn, klim = jax.random.split(key, 6)

            # --- aging: individuals past their Gaussian-drawn limit die
            # (elitism: the island's best individual never ages out).
            elite = jnp.argmin(jnp.where(alive, fit, jnp.inf))
            died = alive & (age > limit) & (jnp.arange(pop) != elite)
            alive = alive & ~died
            fit = jnp.where(alive, fit, jnp.inf)

            # --- roulette-wheel selection among the living (minimization ->
            # weight by distance from the worst finite fitness).
            finite = jnp.where(jnp.isfinite(fit), fit, -jnp.inf)
            worst = jnp.max(finite)
            w = jnp.where(alive, jnp.maximum(worst - fit, 0.0) + 1e-9, 0.0)
            logw = jnp.log(w + 1e-30)
            parents = jax.random.categorical(ksel, logw, shape=(2, n_off))
            p1, p2 = p[parents[0]], p[parents[1]]

            # --- 1-pt crossover with probability pc
            cut = jax.random.randint(kcut, (n_off, 1), 1, dim)
            do_co = (jax.random.uniform(kco, (n_off, 1)) < pc)
            mask = jnp.arange(dim)[None, :] < cut
            child = jnp.where(do_co & mask | ~do_co, p1, p2)

            # --- per-allele Gaussian mutation with probability pm
            mmask = jax.random.uniform(kmm, (n_off, dim)) < pm
            child = child + jnp.where(
                mmask, sigma_m * jax.random.normal(kmn, (n_off, dim)), 0.0)
            child = clip_box(child, lo, hi)
        cfit = evaluator(child)

        with obs.scope(obs.SELECT):
            # --- placement: offspring land in the worst slots (dead slots
            # first, since they carry +inf fitness); only if they improve
            # that slot.
            order = jnp.argsort(fit)[::-1][:n_off]       # worst n_off slots
            slot_f = fit[order]
            take = cfit < slot_f
            p = p.at[order].set(jnp.where(take[:, None], child, p[order]))
            fit = fit.at[order].set(jnp.where(take, cfit, slot_f))
            age = age.at[order].set(jnp.where(take, 0.0, age[order]))
            limit = limit.at[order].set(
                jnp.where(take, draw_limits(klim, n_off).astype(jnp.float32),
                          limit[order]))
            alive = alive.at[order].set(alive[order] | take)

            i = jnp.argmin(fit)
            better = fit[i] < state["best_val"]
            return {
                "pop": p, "fit": fit, "age": age, "age_limit": limit,
                "alive": alive,
                "best_val": jnp.where(better, fit[i], state["best_val"]),
                "best_arg": jnp.where(better, p[i], state["best_arg"]),
            }

    step_override = None
    if fused:
        spec = kreg.get_spec(f.name)   # KeyError if no kernel for this objective
        assert spec.fused_de, f.name

        def gen_fused(state: State, key: Array) -> State:
            # Identical pre-kernel phases (aging, roulette, draws) and key
            # discipline as gen; the (n_off, D) crossover/mutation/eval/
            # placement middle runs in the fused kernel.
            p, fit = state["pop"], state["fit"]
            with obs.scope(obs.VARIATION):
                age, limit = state["age"] + 1.0, state["age_limit"]
                alive = state["alive"]
                ksel, kcut, kco, kmm, kmn, klim = jax.random.split(key, 6)

                elite = jnp.argmin(jnp.where(alive, fit, jnp.inf))
                died = alive & (age > limit) & (jnp.arange(pop) != elite)
                alive = alive & ~died
                fit = jnp.where(alive, fit, jnp.inf)

                finite = jnp.where(jnp.isfinite(fit), fit, -jnp.inf)
                worst = jnp.max(finite)
                wgt = jnp.where(alive, jnp.maximum(worst - fit, 0.0) + 1e-9,
                                0.0)
                logw = jnp.log(wgt + 1e-30)
                parents = jax.random.categorical(ksel, logw, shape=(2, n_off))
                p1, p2 = p[parents[0]], p[parents[1]]

                cut = jax.random.randint(kcut, (n_off, 1), 1, dim)
                co = jax.random.uniform(kco, (n_off, 1))
                um = jax.random.uniform(kmm, (n_off, dim))
                nz = jax.random.normal(kmn, (n_off, dim))

            with obs.scope(obs.SELECT):
                order = jnp.argsort(fit)[::-1][:n_off]   # worst n_off slots
                slot, slot_f = p[order], fit[order]
            with obs.scope(obs.FUSED):
                nslot, nslot_f, take = _ga_step_kernel(
                    p1, p2, slot, slot_f, cut[:, 0], co[:, 0], um, nz,
                    fn=spec.eval_tag, shift=f.shift, bias=f.bias, pc=pc,
                    pm=pm, sigma_m=sigma_m, lo=lo, hi=hi,
                    interpret=interpret, kernel_cfg=kernel_cfg,
                )
            with obs.scope(obs.SELECT):
                p = p.at[order].set(nslot)
                fit = fit.at[order].set(nslot_f)
                age = age.at[order].set(jnp.where(take, 0.0, age[order]))
                limit = limit.at[order].set(
                    jnp.where(take, draw_limits(klim, n_off).astype(jnp.float32),
                              limit[order]))
                alive = alive.at[order].set(alive[order] | take)

                i = jnp.argmin(fit)
                better = fit[i] < state["best_val"]
                return {
                    "pop": p, "fit": fit, "age": age, "age_limit": limit,
                    "alive": alive,
                    "best_val": jnp.where(better, fit[i], state["best_val"]),
                    "best_arg": jnp.where(better, p[i], state["best_arg"]),
                }

        step_override = gen_fused

    return MetaHeuristic("ga", init, gen, evals_per_gen=n_off, init_evals=pop,
                         step_override=step_override)
