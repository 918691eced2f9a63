"""Distributed batch function evaluation — popt4jlib ``parallel.distributed`` in JAX.

The Java library's ``PDBatchTaskExecutorSrv/Clt/Wrk`` network distributes an array of
``TaskObject``s by splitting it into equal-size chunks, one per available worker, and
re-submitting failed batches once. On a TPU mesh the worker pool is the mesh itself:

  * equal-size chunking  -> sharding the population axis over a mesh axis
                            (``shard_map`` with a padded, evenly divisible axis)
  * init-cmd broadcast   -> replicated closure state (captured constants are
                            broadcast to every device by XLA)
  * retry-once-then-evict -> non-finite results are re-evaluated once on a slightly
                            perturbed argument; still-bad results are marked +inf
                            (the candidate is "evicted" from selection)
  * accumulator/reducer  -> the caller reduces with jnp/min-collectives

The *evaluation backend* — how one chunk of candidates becomes fitness values —
is pluggable (POLO-style policy/execution separation, DESIGN.md §3):

  * ``xla``     vmap of the pure-jnp definition; works for every function.
  * ``pallas``  dispatch to the fused ``bench_eval`` VMEM kernel for functions
                with an entry in ``kernels.registry`` (interpret mode off-TPU,
                so CPU tests exercise the same code path).

Both compose with the shard_map wrapper: the mesh distributes chunks, the
backend evaluates each chunk. The executor is a *pure function* of its inputs,
so XLA can fuse it into the surrounding generation step — the distributed
map/reduce costs nothing extra when the mesh is trivial (CPU tests) and lowers
to balanced SPMD on the pod.

Under the island-sharded engine (``core.mesh.MeshConfig``, DESIGN.md §8) the
executor is *per-shard*: the engine traces the plain (``mesh_axis=None``)
evaluator inside its own ``shard_map``, so each device's island block carries
its own EvalBackend instance and no nested shard_map is ever built — the
population-sharding path below is for the single-island Table-I layout only.

The evaluator cache below also serves the hybrid memetic layer (DESIGN.md §6):
``IslandOptimizer._polish`` rebuilds the evaluator for its gradient probes and
line-search ladders and — because ``make_batch_evaluator`` memoizes on
(objective, config, mesh) — receives the SAME callable the generation steps
use, keeping polish on the identical xla/pallas path with zero extra compiles.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import obs
from repro.core.mesh import shard_map as _shard_map
from repro.functions.benchmarks import Function
from repro.kernels import registry as kreg
from repro.kernels.autotune import KernelConfig
from repro.kernels.bench_eval import bench_eval as _bench_eval

Array = jax.Array

BACKENDS = ("xla", "pallas")


@dataclasses.dataclass(frozen=True)
class ExecutorConfig:
    """How candidate batches are evaluated: backend choice, retry policy and
    the mesh axis the population is sharded over (DESIGN.md §3)."""

    backend: str = "xla"          # evaluation backend: "xla" | "pallas"
    retry_bad: bool = True        # paper: resubmit a failed batch once
    retry_eps: float = 1e-6       # perturbation used for the retry evaluation
    mesh_axis: str | tuple[str, ...] | None = None  # population-sharding axis(es)
    interpret: bool | None = None # pallas interpret mode; None = auto (off-TPU)
    # One KernelConfig threaded to EVERY Pallas kernel entry this config
    # touches — the pallas eval backend here and the fused generation kernels
    # the engine builds (islands/portfolio inject it into policy makers).
    # Unset fields are autotuned per shape-class by kernels.autotune.
    kernel: KernelConfig = KernelConfig()


def _pallas_interpret(cfg: ExecutorConfig) -> bool:
    if cfg.interpret is not None:
        return cfg.interpret
    if cfg.kernel.interpret is not None:
        return cfg.kernel.interpret
    return jax.default_backend() != "tpu"


def _make_eval_once(f: Function, cfg: ExecutorConfig) -> Callable[[Array], Array]:
    """Resolve the per-chunk evaluation backend for ``f``."""
    if cfg.backend == "xla":
        return lambda pop: jax.vmap(f.fn)(pop)
    if cfg.backend == "pallas":
        spec = kreg.get_spec(f.name)   # KeyError for unregistered functions
        kc = dataclasses.replace(cfg.kernel, interpret=_pallas_interpret(cfg))

        def eval_pallas(pop: Array) -> Array:
            return _bench_eval(pop, spec.eval_tag, shift=f.shift,
                               bias=f.bias, kernel_cfg=kc)

        return eval_pallas
    raise ValueError(f"unknown backend {cfg.backend!r}; expected one of {BACKENDS}")


# Per-bucket evaluator cache: the scheduler rebuilds an optimizer per bucket
# flush, and a stable evaluator identity keeps the downstream jit caches warm
# (a fresh closure would recompile every generation step). Keyed by
# Function.cache_token() — a GC-stable identity token plus the shift content,
# so a recycled id() can never silently alias a dead objective or a dead
# shift array — plus config and mesh; values still carry the live objects as
# a belt-and-braces identity guard. FIFO-capped: keys are request-controlled,
# so an adversarial traffic mix must recompile rather than grow memory
# unboundedly.
_EVALUATOR_CACHE: dict[tuple, tuple] = {}
_EVALUATOR_CACHE_MAX = 256


def make_batch_evaluator(
    f: Function,
    cfg: ExecutorConfig = ExecutorConfig(),
    mesh: Mesh | None = None,
) -> Callable[[Array], Array]:
    """Return ``evaluate(pop: (P, D)) -> (P,)`` with the executor semantics above.

    Evaluators are memoized on ``(objective identity, cfg, mesh identity)`` —
    repeated builds for the same shape-class (scheduler buckets, benchmark
    loops) return the same callable.
    """
    # id(mesh) is safe here because live cache entries hold the mesh strongly
    # (hit[1]), so a colliding recycled address always fails the identity
    # guard below and rebuilds instead of serving a stale program.
    ck = (*f.cache_token(), cfg, id(mesh))
    hit = _EVALUATOR_CACHE.get(ck)
    if hit is not None and hit[0] is f.fn and hit[1] is mesh:
        return hit[2]

    _eval_once = _make_eval_once(f, cfg)

    def evaluate(pop: Array) -> Array:
        with obs.scope(obs.EVALUATE):
            fit = _eval_once(pop)
        if cfg.retry_bad:
            with obs.scope(obs.RETRY):
                bad = ~jnp.isfinite(fit)
                # Retry the failed "batch" once on a perturbed argument (the
                # SPMD analogue of handing the task to another worker).
                retried = _eval_once(pop + cfg.retry_eps)
                fit = jnp.where(bad, retried, fit)
                # Second failure -> evict from the candidate pool.
                fit = jnp.where(jnp.isfinite(fit), fit, jnp.inf)
        return fit

    if mesh is None or cfg.mesh_axis is None:
        _cache_put(ck, (f.fn, mesh, evaluate))
        return evaluate

    axis = cfg.mesh_axis
    spec_in = P(axis, None)
    spec_out = P(axis)

    def sharded_evaluate(pop: Array) -> Array:
        # Equal-size chunks per worker: pad P to a multiple of the axis size.
        n = 1
        for a in (axis if isinstance(axis, tuple) else (axis,)):
            n *= mesh.shape[a]
        pcount = pop.shape[0]
        pad = (-pcount) % n
        padded = jnp.pad(pop, ((0, pad), (0, 0)))
        out = _shard_map(
            evaluate, mesh, in_specs=(spec_in,), out_specs=spec_out,
        )(padded)
        return out[:pcount]

    _cache_put(ck, (f.fn, mesh, sharded_evaluate))
    return sharded_evaluate


def _cache_put(key: tuple, val: tuple) -> None:
    _EVALUATOR_CACHE[key] = val
    while len(_EVALUATOR_CACHE) > _EVALUATOR_CACHE_MAX:
        _EVALUATOR_CACHE.pop(next(iter(_EVALUATOR_CACHE)))


def distributed_map_reduce(
    mesh: Mesh,
    axis: str,
    map_fn: Callable[[Array], Array],
    reduce_op: str,
    xs: Array,
) -> Array:
    """popt4jlib distributed map/reduce operator: map over the sharded leading axis,
    reduce with a collective (the "accumulator server")."""

    def body(chunk: Array) -> Array:
        mapped = jax.vmap(map_fn)(chunk)
        local = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[reduce_op](mapped, axis=0)
        return jax.lax.psum(local, axis) if reduce_op == "sum" else (
            jax.lax.pmin(local, axis) if reduce_op == "min" else jax.lax.pmax(local, axis)
        )

    return _shard_map(
        body, mesh, in_specs=(P(axis),), out_specs=P(),
    )(xs)
