"""DDE — island-model Differential Evolution (popt4jlib.DE).

Implements DE/rand/1/bin and DE/best/1/bin (the paper's two variants) and the
paper's "non-determinism-ok" flag:

  barrier_mode="sync"     the barrier-corrected semantics: every trial vector of a
                          generation reads the *same* snapshot of the population
                          (deterministic in Java only with the barrier; always
                          deterministic here).
  barrier_mode="chunked"  the barrier-free semantics: the population is updated in
                          ``n_chunks`` blocks and later blocks read earlier blocks'
                          fresh writes — the reproducible SPMD analogue of the Java
                          threads racing on the shared solution array. One fewer
                          population snapshot per generation (cheaper on TPU: no
                          second all-gather when the population axis is sharded).

Key discipline: a generation's ``key`` splits into selection, crossover and
forced-coordinate keys as in ``_trials``; chunk ``c`` of chunked mode keys on
``fold_in(key, c)``. No key reads the population, so chunked mode draws every
chunk's donors, forced coordinates and crossover key before its chunk loop
(``_chunk_draws``; through ``MetaHeuristic.draw`` the engine makes them for a
whole round before its scan), and a chunk hashes only its own crossover
uniforms. Each chunk keeps its rows of those full-population integer draws by
static slices, since its start is known when tracing; a gather would pick
them one element at a time on the TPU.

``fused=True`` routes the whole generation — mutation, crossover, evaluation,
selection — through the fused ``kernels.de_step`` Pallas kernel (one HBM read /
write of the population instead of five round-trips) via the engine's
``step_override`` hook. Requires DE/rand/1/bin and an objective registered in
``kernels.registry``; runs in interpret mode off-TPU so the same path is
exercised on CPU.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from repro.core import obs
from repro.core.islands import MetaHeuristic, State, clip_box, track_best, uniform_init
from repro.functions.benchmarks import Function
from repro.kernels import registry as kreg
from repro.kernels.autotune import KernelConfig
from repro.kernels.de_step import de_step as _de_step_kernel

Array = jax.Array


def _distinct3(key: Array, P: int) -> tuple[Array, Array, Array]:
    """Three random indices per row, each != the row index (mod-shift trick)."""
    i = jnp.arange(P)
    k1, k2, k3 = jax.random.split(key, 3)
    ra = (i + 1 + jax.random.randint(k1, (P,), 0, P - 1)) % P
    rb = (i + 1 + jax.random.randint(k2, (P,), 0, P - 1)) % P
    rc = (i + 1 + jax.random.randint(k3, (P,), 0, P - 1)) % P
    return ra, rb, rc


def _trials(pop: Array, best: Array, key: Array, w: float, px: float,
            strategy: str) -> Array:
    P, D = pop.shape
    ksel, kcr, kj = jax.random.split(key, 3)
    ra, rb, rc = _distinct3(ksel, P)
    base = pop[ra] if strategy == "rand1bin" else jnp.broadcast_to(best, pop.shape)
    mutant = base + w * (pop[rb] - pop[rc])
    # binomial crossover with a guaranteed dimension
    cross = jax.random.uniform(kcr, (P, D)) < px
    jrand = jax.random.randint(kj, (P,), 0, D)
    cross = cross | (jnp.arange(D)[None, :] == jrand[:, None])
    return jnp.where(cross, mutant, pop)


def _uniform_rows(key: Array, P: int, D: int, start: Array, n: int) -> Array:
    """Rows ``[start, start + n)`` of ``jax.random.uniform(key, (P, D))``, bit
    for bit, drawing only those rows.

    Partitionable threefry (JAX's default) makes element ``i`` of a draw the
    hash of ``key`` and its flat index ``i``: this is
    ``jax._src.prng._threefry_random_bits_partitionable`` followed by
    ``uniform``'s float32 mantissa trick, on the block's counters alone (XLA
    does not push a slice of a full draw into the hash). Any other setting
    (non-partitionable threefry, another PRNG implementation, 64-bit floats,
    a draw of 2**32 or more counters) takes the full draw and slices it.
    """
    typed = jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)
    impl = (str(jax.random.key_impl(key)) if typed
            else jax.config.jax_default_prng_impl)
    if not (impl == "threefry2x32" and jax.config.jax_threefry_partitionable
            and not jax.config.jax_enable_x64 and P * D < 2**32):
        return jax.lax.dynamic_slice_in_dim(
            jax.random.uniform(key, (P, D)), start, n, 0)
    return _threefry_uniform_rows(jax.random.key_data(key) if typed else key,
                                  start, n, D)


@partial(jax.jit, static_argnums=(2, 3))
def _threefry_uniform_rows(k: Array, start: Array, n: int, D: int) -> Array:
    # Its own jit, as jax.random.uniform is, so the ops' names do not take
    # the caller's phase scope.
    lo = (jnp.asarray(start, jnp.uint32) * jnp.uint32(D)
          + jax.lax.iota(jnp.uint32, n * D)).reshape(n, D)
    b1, b2 = threefry2x32_p.bind(k[0], k[1], jnp.zeros_like(lo), lo)
    bits = ((b1 ^ b2) >> 9) | jnp.uint32(0x3F800000)
    return jnp.maximum(0.0, jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0)


def _chunk_draws(key: Array, P: int, D: int, n: int, n_chunks: int
                 ) -> tuple[tuple[Array, Array, Array], Array, Array]:
    """Every chunk's donors, forced coordinates and crossover key, drawn once.

    Chunk ``c`` keys on ``fold_in(key, c)`` and keeps rows ``[start_c,
    start_c + n)``, ``start_c = min(c * n, P - n)``, of the P-row draws
    ``_trials`` makes from it (the last chunk overlaps its predecessor when
    ``n`` does not divide ``P``). No key reads the population, so one vmap
    over ``c`` draws them all before the chunk loop. The starts are static,
    so each chunk's rows are cut by a static slice of its four draws and the
    slices stacked. Not by a gather with constant indices: the TPU lowers
    that one element at a time, and it cost more than the chunk's trial and
    evaluation together. Nor by a ``dynamic_slice`` with a batched start,
    which lowers to a loop per draw. Returns ``(ra, rb, rc)``, ``jrand``
    (each ``(n_chunks, n)``) and the ``n_chunks`` crossover keys."""
    def draw(c: Array) -> tuple[Array, Array]:
        ksel, kcr, kj = jax.random.split(jax.random.fold_in(key, c), 3)
        return jnp.stack([*_distinct3(ksel, P),
                          jax.random.randint(kj, (P,), 0, D)]), kcr

    rows, kcr = jax.vmap(draw)(jnp.arange(n_chunks))
    starts = np.minimum(np.arange(n_chunks) * n, P - n)
    d = jnp.stack([rows[c, :, s:s + n] for c, s in enumerate(starts)])
    return (d[:, 0], d[:, 1], d[:, 2]), d[:, 3], kcr


def _chunk_trials(pop: Array, best: Array, donors: tuple[Array, Array, Array],
                  jrand: Array, kcr: Array, start: Array, w: float, px: float,
                  strategy: str) -> Array:
    """Rows ``[start, start + n)`` of ``_trials(pop, best, key, ...)``, bit for
    bit, from that chunk's rows of ``_chunk_draws``: the donors are gathered
    from the current population, the crossover uniforms drawn for the block
    alone (XLA fuses that hash into the trial's arithmetic)."""
    ra, rb, rc = donors
    n, D = ra.shape[0], pop.shape[1]
    base = pop[ra] if strategy == "rand1bin" else jnp.broadcast_to(best, (n, D))
    mutant = base + w * (pop[rb] - pop[rc])
    cross = _uniform_rows(kcr, pop.shape[0], D, start, n) < px
    cross = cross | (jnp.arange(D)[None, :] == jrand[:, None])
    return jnp.where(cross, mutant, jax.lax.dynamic_slice_in_dim(pop, start, n, 0))


def make(
    f: Function,
    evaluator: Callable[[Array], Array],
    pop: int,
    dim: int,
    w: float = 0.5,
    px: float = 0.2,
    strategy: str = "rand1bin",        # rand1bin | best1bin
    barrier_mode: str = "sync",        # sync | chunked ("non-determinism-ok")
    n_chunks: int = 8,
    fused: bool = False,               # whole generation in one Pallas kernel
    interpret: bool | None = None,     # fused-kernel interpret mode; None = auto
    kernel_cfg: KernelConfig | None = None,
) -> MetaHeuristic:
    """Differential Evolution per-island policy (DE/rand/1/bin, DE/best/1/bin)."""
    assert strategy in ("rand1bin", "best1bin")
    assert barrier_mode in ("sync", "chunked")
    lo, hi = f.lo, f.hi

    def init(key: Array) -> State:
        p = uniform_init(key, pop, dim, lo, hi)
        fit = evaluator(p)
        i = jnp.argmin(fit)
        return {"pop": p, "fit": fit, "best_arg": p[i], "best_val": fit[i]}

    def gen_sync(state: State, key: Array) -> State:
        p, fit = state["pop"], state["fit"]
        with obs.scope(obs.VARIATION):
            trial = clip_box(_trials(p, state["best_arg"], key, w, px, strategy),
                             lo, hi)
        tfit = evaluator(trial)
        with obs.scope(obs.SELECT):
            better = tfit <= fit
            p = jnp.where(better[:, None], trial, p)
            fit = jnp.where(better, tfit, fit)
            return track_best(state, p, fit)

    csz = max(1, pop // n_chunks) if barrier_mode == "chunked" else pop
    n_eff_chunks = (pop + csz - 1) // csz

    def draw_chunked(key: Array) -> tuple:
        with obs.scope(obs.VARIATION):
            return _chunk_draws(key, pop, dim, csz, n_eff_chunks)

    def gen_chunked(state: State, key: Array, drawn: tuple | None = None
                    ) -> State:
        # ``drawn`` is ``draw_chunked(key)``, made ahead by the engine.
        donors, jrand, kcr = draw_chunked(key) if drawn is None else drawn

        # Later chunks read earlier chunks' already-updated vectors ("stale-ok").
        def body(c: int, carry: tuple[Array, Array]) -> tuple[Array, Array]:
            p, fit = carry
            with obs.scope(obs.VARIATION):
                start = jnp.minimum(c * csz, pop - csz)
                trial = clip_box(_chunk_trials(
                    p, p[jnp.argmin(fit)], tuple(r[c] for r in donors),
                    jrand[c], kcr[c], start, w, px, strategy), lo, hi)
            with obs.scope(obs.SELECT):
                cur_f = jax.lax.dynamic_slice_in_dim(fit, start, csz, 0)
                cur_p = jax.lax.dynamic_slice_in_dim(p, start, csz, 0)
            tfit = evaluator(trial)
            with obs.scope(obs.SELECT):
                better = tfit <= cur_f
                newp = jnp.where(better[:, None], trial, cur_p)
                newf = jnp.where(better, tfit, cur_f)
                p = jax.lax.dynamic_update_slice_in_dim(p, newp, start, 0)
                fit = jax.lax.dynamic_update_slice_in_dim(fit, newf, start, 0)
            return p, fit

        p, fit = jax.lax.fori_loop(0, n_eff_chunks, body, (state["pop"], state["fit"]))
        with obs.scope(obs.SELECT):
            return track_best(state, p, fit)

    step_override = None
    if fused:
        assert strategy == "rand1bin", "fused DE implements DE/rand/1/bin only"
        spec = kreg.get_spec(f.name)   # KeyError if no kernel for this objective
        assert spec.fused_de, f.name

        def gen_fused(state: State, key: Array) -> State:
            # Same key discipline as gen_sync/_trials, so the fused and XLA
            # paths draw identical donors/crossover masks on a fixed seed.
            with obs.scope(obs.VARIATION):
                ksel, kcr, kj = jax.random.split(key, 3)
                ra, rb, rc = _distinct3(ksel, pop)
                u = jax.random.uniform(kcr, (pop, dim))
                jrand = jax.random.randint(kj, (pop,), 0, dim)
                donors = jnp.stack([ra, rb, rc])
            with obs.scope(obs.FUSED):
                new_pop, new_fit = _de_step_kernel(
                    state["pop"], state["fit"], donors, u, jrand,
                    fn=spec.eval_tag, shift=f.shift, bias=f.bias,
                    w=w, px=px, lo=lo, hi=hi, interpret=interpret,
                    kernel_cfg=kernel_cfg,
                )
            with obs.scope(obs.SELECT):
                return track_best(state, new_pop, new_fit)

        step_override = gen_fused

    gen = gen_sync if barrier_mode == "sync" else gen_chunked
    # Chunked mode evaluates n_eff_chunks fixed-size blocks of csz rows; when
    # csz does not divide pop the clamped slices overlap and the generation
    # really consumes csz * n_eff_chunks evaluations, not pop — charge what
    # the evaluator actually runs (parity enforced for every registered
    # policy by tests/test_metaheuristics.py::test_evals_per_gen_parity).
    evals = csz * n_eff_chunks if barrier_mode == "chunked" else pop
    draw = draw_chunked if gen is gen_chunked and not fused else None
    return MetaHeuristic("de", init, gen, evals_per_gen=evals, init_evals=pop,
                         step_override=step_override, draw=draw)
