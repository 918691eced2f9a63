"""DSA — multi-threaded Simulated Annealing with multi-point restarts
(popt4jlib.SA, after Ram–Sreenivas–Subramaniam [8]).

The Java class runs one chain per thread; here the chains are the rows of a
(P, D) array (vmapped; sharded by the engine). All four cooling schedules of
popt4jlib.SA.SAScheduleIntf are provided: linear, exponential, Boltzmann, Cauchy.
Fig.4 setup: linear schedule from T0=1000 down to 0 over the run.

``fused=True`` routes the evaluate-and-accept tail through the fused
``kernels.eval_select`` Pallas kernel via ``step_override``: the Metropolis
rule ``u < exp(-dF/T)`` is algebraically a per-row threshold test
``dF < -T*ln(u)``, which is exactly the kernel's acceptance form (greedy is
the ``thresh=0`` special case). Same key discipline as the XLA path.
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
from repro.kernels.eval_select import eval_select as _eval_select_kernel

Array = jax.Array

SCHEDULES = {
    "linear": lambda t, T0, n: T0 * jnp.maximum(1.0 - t / n, 0.0),
    "exponential": lambda t, T0, n: T0 * (0.99 ** t),
    "boltzmann": lambda t, T0, n: T0 / jnp.log(t + jnp.e),
    "cauchy": lambda t, T0, n: T0 / (1.0 + t),
}


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
    schedule: str = "linear",
    T0: float = 1000.0,
    n_gens_hint: int = 10_000,   # horizon for the linear schedule
    step_frac: float = 0.1,      # proposal sigma as a fraction of the box width
    fused: bool = False,         # evaluate+accept in one Pallas kernel
    interpret: bool | None = None,
    kernel_cfg: KernelConfig | None = None,
) -> MetaHeuristic:
    """Simulated Annealing per-island policy (population of parallel chains)."""
    lo, hi = f.lo, f.hi
    sched = SCHEDULES[schedule]
    sigma = step_frac * (hi - lo)

    def init(key: Array) -> State:
        x = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(x)
        i = jnp.argmin(fit)
        return {
            "pop": x, "fit": fit, "t": jnp.zeros((), jnp.float32),
            "best_arg": x[i], "best_val": fit[i],
        }

    def gen(state: State, key: Array) -> State:
        x, fx, t = state["pop"], state["fit"], state["t"]
        with obs.scope(obs.VARIATION):
            kp, ka = jax.random.split(key)
            T = sched(t, T0, float(n_gens_hint))
            y = clip_box(x + sigma * jax.random.normal(kp, x.shape), lo, hi)
        fy = evaluator(y)
        with obs.scope(obs.SELECT):
            dF = fy - fx
            u = jax.random.uniform(ka, fx.shape)
            accept = (dF <= 0) | (u < jnp.exp(-dF / jnp.maximum(T, 1e-12)))
            x = jnp.where(accept[:, None], y, x)
            fx = jnp.where(accept, fy, fx)
            i = jnp.argmin(fx)
            better = fx[i] < state["best_val"]
            return {
                "pop": x, "fit": fx, "t": t + 1.0,
                "best_val": jnp.where(better, fx[i], state["best_val"]),
                "best_arg": jnp.where(better, x[i], state["best_arg"]),
            }

    step_override = None
    if fused:
        spec = kreg.get_spec(f.name)   # KeyError if no kernel for this objective
        assert spec.fused_de, f.name

        def gen_fused(state: State, key: Array) -> State:
            x, fx, t = state["pop"], state["fit"], state["t"]
            with obs.scope(obs.VARIATION):
                kp, ka = jax.random.split(key)
                T = sched(t, T0, float(n_gens_hint))
                y = clip_box(x + sigma * jax.random.normal(kp, x.shape), lo, hi)
                u = jax.random.uniform(ka, fx.shape)
                # Metropolis as a threshold: u < exp(-dF/T) <=> dF < -T*ln(u)
                thresh = -jnp.maximum(T, 1e-12) * jnp.log(u)
            with obs.scope(obs.FUSED):
                x, fx, _ = _eval_select_kernel(
                    x, fx, y, thresh, fn=spec.eval_tag, shift=f.shift,
                    bias=f.bias, interpret=interpret, kernel_cfg=kernel_cfg,
                )
            with obs.scope(obs.SELECT):
                i = jnp.argmin(fx)
                better = fx[i] < state["best_val"]
                return {
                    "pop": x, "fit": fx, "t": t + 1.0,
                    "best_val": jnp.where(better, fx[i], state["best_val"]),
                    "best_arg": jnp.where(better, x[i], state["best_arg"]),
                }

        step_override = gen_fused

    return MetaHeuristic("sa", init, gen, evals_per_gen=pop, init_evals=pop,
                         step_override=step_override)
