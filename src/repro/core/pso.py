"""DPSO — island-model Particle Swarm Optimization (popt4jlib.PS).

Velocity/position update with inertia w and cognitive/social factors f_p/f_g
(Fig.4 setup: w=0.6, f_p=f_g=1). The island's gbest is the SelectorIntf
"topology" (default: global-within-island); inter-island exchange uses the
engine's counter-clock-wise ring — the paper's DPSO default.

``fused=True`` routes the whole generation — velocity/position update,
evaluation, pbest selection — through the fused ``kernels.pso_step`` Pallas
kernel via the engine's ``step_override`` hook (same key discipline as the
XLA path, so both are bit-comparable on a fixed seed). Requires an objective
registered in ``kernels.registry``.
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
from repro.kernels.pso_step import pso_step as _pso_step_kernel

Array = jax.Array


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
    w: float = 0.6,
    fp: float = 1.0,
    fg: float = 1.0,
    vmax_frac: float = 0.2,
    fused: bool = False,               # whole generation in one Pallas kernel
    interpret: bool | None = None,     # fused-kernel interpret mode; None = auto
    kernel_cfg: KernelConfig | None = None,
) -> MetaHeuristic:
    """Particle Swarm per-island policy (inertia w, cognitive fp, social fg)."""
    lo, hi = f.lo, f.hi
    vmax = vmax_frac * (hi - lo)

    def init(key: Array) -> State:
        kx, kv = jax.random.split(key)
        x = uniform_init(kx, pop, dim, lo, hi)
        v = vmax * (jax.random.uniform(kv, (pop, dim)) - 0.5)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {
            "pop": x, "fit": fit, "vel": v,
            # distinct buffers: the engine donates the state at round boundaries
            "pbest": jnp.copy(x), "pbest_f": jnp.copy(fit),
            "best_arg": x[i], "best_val": fit[i],
        }

    def gen(state: State, key: Array) -> State:
        x, v = state["pop"], state["vel"]
        with obs.scope(obs.VARIATION):
            k1, k2 = jax.random.split(key)
            r1 = jax.random.uniform(k1, x.shape)
            r2 = jax.random.uniform(k2, x.shape)
            v = (w * v + fp * r1 * (state["pbest"] - x)
                 + fg * r2 * (state["best_arg"] - x))
            v = jnp.clip(v, -vmax, vmax)
            x = clip_box(x + v, lo, hi)
        fit = evaluator(x)

        with obs.scope(obs.SELECT):
            imp = fit < state["pbest_f"]
            pbest = jnp.where(imp[:, None], x, state["pbest"])
            pbest_f = jnp.where(imp, fit, state["pbest_f"])
            i = jnp.argmin(pbest_f)
            better = pbest_f[i] < state["best_val"]
            return {
                "pop": x, "fit": fit, "vel": v, "pbest": pbest,
                "pbest_f": pbest_f,
                "best_val": jnp.where(better, pbest_f[i], state["best_val"]),
                "best_arg": jnp.where(better, pbest[i], state["best_arg"]),
            }

    step_override = None
    if fused:
        spec = kreg.get_spec(f.name)   # KeyError if no kernel for this objective
        assert spec.fused_de, f.name

        def gen_fused(state: State, key: Array) -> State:
            # Same key discipline as gen, so fused and XLA paths draw
            # identical r1/r2 on a fixed seed.
            with obs.scope(obs.VARIATION):
                k1, k2 = jax.random.split(key)
                r1 = jax.random.uniform(k1, (pop, dim))
                r2 = jax.random.uniform(k2, (pop, dim))
            with obs.scope(obs.FUSED):
                nx, nv, fit, npb, npbf = _pso_step_kernel(
                    state["pop"], state["vel"], state["pbest"],
                    state["pbest_f"], r1, r2, state["best_arg"],
                    fn=spec.eval_tag, shift=f.shift, bias=f.bias, w=w, fp=fp,
                    fg=fg, vmax=vmax, lo=lo, hi=hi, interpret=interpret,
                    kernel_cfg=kernel_cfg,
                )
            with obs.scope(obs.SELECT):
                i = jnp.argmin(npbf)
                better = npbf[i] < state["best_val"]
                return {
                    "pop": nx, "fit": fit, "vel": nv, "pbest": npb,
                    "pbest_f": npbf,
                    "best_val": jnp.where(better, npbf[i], state["best_val"]),
                    "best_arg": jnp.where(better, npb[i], state["best_arg"]),
                }

        step_override = gen_fused

    return MetaHeuristic("pso", init, gen, evals_per_gen=pop, init_evals=pop,
                         step_override=step_override)
