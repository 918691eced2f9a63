"""Island-model engine — the unified runtime behind DGA/DDE/DPSO/DSA/DEA/DFA/DGABH/MCS.

Java design: one island per thread, migration over shared memory / sockets,
fitness evaluation optionally farmed to a worker network.

JAX design: islands are the leading axis of every state leaf, `vmap`-ed per
generation and sharded over the mesh's (pod, data) axes; migration is an
array roll/gather over that axis (lowers to collective-permute / all-gather);
the incumbent all-reduce at each sync round realizes the Observer pattern
between islands. One *sync round* = `sync_every` generations + migration +
incumbent merge.

Passing a ``core.mesh.MeshConfig`` makes the engine *device-parallel*
(DESIGN.md §8): the island axis is laid out over a 1-D device mesh and the
whole round scan runs under ``shard_map``, each shard owning
``n_islands / devices`` islands with its own EvalBackend instance. Ring
migration crosses shard boundaries as a single ``lax.ppermute`` exchange;
starvation and incumbent sharing degrade to all-gathers on the sync cadence.
A fixed seed on a 1-device mesh is bit-identical to the unsharded engine —
the determinism contract ``tests/test_distributed.py`` enforces.

The engine is *device-resident*: the whole run is one jitted
``lax.scan`` over sync rounds with donated state and an on-device
``(n_rounds,)`` incumbent-history buffer, and results cross to the host
exactly once at the end (DESIGN.md §4). ``BucketStepper`` is the one
host-stepped runner: it runs the same round body one jit call per round, so
the service can stream progress, cancel, checkpoint and resume at round
granularity.

``IslandConfig.polish`` turns any meta-heuristic into a *memetic hybrid*
(DESIGN.md §6): every ``polish_every`` rounds, each island's ``polish_topk``
best candidates pass through a batched fixed-shape local descent
(``optim.descent.make_polish`` — the paper's ``LocalOptimizerIntf``) inside
the same jitted scan, with polish evaluations charged to ``max_evals``. The
polish pass is deterministic, so fixed-seed trajectories stay reproducible
through both ``minimize`` and ``minimize_many``.

``IslandConfig.portfolio`` makes the engine *heterogeneous* (DESIGN.md §10):
each island carries its own policy from ``core.portfolio``'s unified-state
registry and the round loop dispatches the generation step through
``lax.switch`` over the portfolio's branch table — a mixed DE+PSO+SA island
set runs inside the SAME jitted scan, composing with migration (migrants
carry pos/fit; destination-policy aux slots re-initialize on adoption),
incumbent sharing, the polish cadence and island sharding. A homogeneous
portfolio skips the switch and is bit-identical to the plain
``algo_maker``-driven engine.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mesh as mesh_mod
from repro.core import migration as mig
from repro.core import obs
from repro.core.api import OptimizeResult
from repro.core.executor import ExecutorConfig, make_batch_evaluator
from repro.core.mesh import MeshConfig
from repro.functions.benchmarks import Function

Array = jax.Array
State = dict[str, Array]


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    """Engine topology + budget: islands, migration, sharding and the hybrid
    memetic polish layer, all fixed before compilation (one shape-class)."""

    n_islands: int = 1
    pop: int = 64                 # per-island population capacity
    dim: int = 10
    sync_every: int = 10          # generations between migration/incumbent rounds
    migration: str = "ring"       # ring | starvation | none
    n_migrants: int = 2           # paper: at most 2 leave an island per round
    share_incumbent: bool = False # device-side Observer: broadcast global best
    max_evals: int = 100_000      # Fig.4 budget unit: function evaluations
    island_axes: tuple[str, ...] = ("data",)  # mesh axes the island dim shards over
    pop_axes: tuple[str, ...] | None = None   # mesh axes the population dim shards
                                              # over when n_islands == 1 (Table I)
    # Hybrid memetic layer (DESIGN.md §6): batched local-descent polish of each
    # island's top-k candidates, inside the jitted round scan. Polish evals are
    # charged to max_evals (see _budget), so hybrid and plain runs compare at
    # equal budgets — the paper's DGA+ASD-style configurations.
    polish: str = "none"          # none | asd | fcg | avd | bfgs
    polish_every: int = 1         # sync rounds between polish events
    polish_topk: int = 4          # per-island candidates polished per event
    polish_steps: int = 3         # descent iterations per polish event
    # Heterogeneous algorithm portfolio (DESIGN.md §10): one policy name per
    # island (cycled round-robin when shorter than n_islands). Non-empty
    # selects portfolio mode — pass algo_maker=None; per-policy params go in
    # IslandOptimizer(params={"de": {...}, ...}).
    portfolio: tuple[str, ...] = ()
    # Async staleness-bounded islands (DESIGN.md §13): "async" drops the
    # global round barrier — islands advance on their own cadence (an
    # AsyncSchedule) and exchange migrants through a fixed-shape mailbox ring
    # (core.migration.mailbox_*) instead of the lockstep exchange. Requires
    # migration in ("ring", "none"); with n_islands == 1 the mailbox is a
    # self-loop no-op and the engine runs the barrier path unchanged. An
    # all-ones schedule with max_staleness=0 degrades bit-identically to the
    # barrier engine (tests/test_async_islands.py).
    sync_policy: str = "barrier"  # barrier | async
    max_staleness: int = 0        # adopt migrants at most this many rounds old
    mailbox_slots: int = 4        # per-island mailbox ring capacity


@dataclasses.dataclass(frozen=True)
class MetaHeuristic:
    """One meta-heuristic = per-island init + generation step + eval accounting.

    ``step_override`` replaces ``gen`` inside the engine's round loop when set —
    the hook a fused whole-generation kernel (e.g. ``de.make(fused=True)``)
    uses to bypass the pluggable evaluator while keeping init, migration,
    incumbent sharing and budget accounting identical.

    ``draw``, when set (never with ``step_override``), makes the part of a
    generation's randomness that reads no state: ``gen(state, key)`` equals
    ``gen(state, key, draw(key))``. The engine then draws a round's
    generations in one vmap before the round's scan, so the scan body starts
    on the population, and on the TPU the population's copy into on-chip
    memory hides behind the draws once a round instead of stalling every
    generation (chunked DE).
    """

    name: str
    init: Callable[[Array], State]          # key -> single-island state
    gen: Callable[..., State]               # (state, key[, drawn]) -> state
    evals_per_gen: int
    init_evals: int
    step_override: Callable[[State, Array], State] | None = None
    draw: Callable[[Array], Any] | None = None


@dataclasses.dataclass(frozen=True)
class AsyncSchedule:
    """Record/replay hook for the async engine's mailbox (DESIGN.md §13).

    ``step[t, i]`` — island ``i`` runs a sync round at tick ``t``;
    ``deliver[t, i]`` — the migrant batch island ``i`` posts at tick ``t``
    reaches its ring successor (False models a dropped message). Both
    default to all-ones — every island on every tick, every delivery on
    time — which is exactly the barrier cadence. A ``seed`` generates random
    Bernoulli masks instead (host-side numpy, so the jitted run only ever
    sees concrete arrays). Whatever arrays a run actually used are recorded
    in ``IslandOptimizer.recorded_schedule``; feeding that schedule back in
    replays the run bit-identically (the record/replay contract
    ``tests/test_async_islands.py`` enforces).
    """

    step: Any = None          # (n_rounds, n_islands) bool, or None
    deliver: Any = None       # (n_rounds, n_islands) bool, or None
    seed: int | None = None   # random masks when the arrays are absent
    step_prob: float = 0.75
    deliver_prob: float = 0.75

    def materialize(self, n_rounds: int, n_islands: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Concrete ``(step, deliver)`` bool masks of shape
        ``(n_rounds, n_islands)`` — explicit arrays are validated, missing
        ones are filled from ``seed`` (or all-ones without one)."""
        rng = np.random.RandomState(0 if self.seed is None else self.seed)

        def mask(a: Any, p: float, name: str) -> np.ndarray:
            if a is not None:
                a = np.asarray(a, dtype=bool)
                if a.shape != (n_rounds, n_islands):
                    raise ValueError(
                        f"AsyncSchedule.{name} has shape {a.shape}, engine "
                        f"needs {(n_rounds, n_islands)}")
                return a
            if self.seed is None:
                return np.ones((n_rounds, n_islands), dtype=bool)
            return rng.random_sample((n_rounds, n_islands)) < p

        return (mask(self.step, self.step_prob, "step"),
                mask(self.deliver, self.deliver_prob, "deliver"))

    @classmethod
    def from_cadences(cls, cadences, n_rounds: int) -> "AsyncSchedule":
        """Deterministic per-island cadence schedule: island ``i`` steps on
        ticks ``t`` with ``t % cadences[i] == 0`` (a straggler with cadence 4
        completes a round every 4th tick); every delivery fires."""
        c = np.asarray(cadences, dtype=int)
        if (c < 1).any():
            raise ValueError("cadences must be >= 1")
        step = (np.arange(n_rounds)[:, None] % c[None, :]) == 0
        return cls(step=step, deliver=np.ones_like(step))


AlgoMaker = Callable[..., MetaHeuristic]


def _accepts_kernel_cfg(maker: AlgoMaker) -> bool:
    """Whether a policy maker declares a ``kernel_cfg`` parameter (the hook
    the engine uses to thread ``ExecutorConfig.kernel`` into fused kernels).
    Custom makers without the parameter are simply not injected into."""
    import inspect
    try:
        return "kernel_cfg" in inspect.signature(maker).parameters
    except (TypeError, ValueError):      # builtins / odd callables
        return False


class IslandOptimizer:
    """popt4jlib OptimizerIntf over the island engine."""

    def __init__(
        self,
        algo_maker: AlgoMaker | None,
        cfg: IslandConfig,
        params: dict[str, Any] | None = None,
        mesh: Mesh | None = None,
        mesh_cfg: MeshConfig | None = None,
        exec_cfg: ExecutorConfig = ExecutorConfig(),
        schedule: AsyncSchedule | None = None,
    ) -> None:
        self.algo_maker = algo_maker
        self.cfg = cfg
        self.params = dict(params or {})
        # Async staleness-bounded mode (DESIGN.md §13). With one island the
        # mailbox is a self-loop no-op, so the engine keeps the barrier path.
        if cfg.sync_policy not in ("barrier", "async"):
            raise ValueError(f"unknown sync_policy {cfg.sync_policy!r}")
        if cfg.sync_policy == "async" and cfg.migration == "starvation":
            raise ValueError(
                "async islands support ring|none migration only: starvation "
                "elects its host by a global argmin over every island's live "
                "count, which is inherently a barrier")
        if cfg.max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        if cfg.mailbox_slots < 1:
            raise ValueError("mailbox_slots must be >= 1")
        self._async = cfg.sync_policy == "async" and cfg.n_islands > 1
        if schedule is not None and not self._async:
            raise ValueError(
                "an AsyncSchedule needs sync_policy='async' and n_islands > 1")
        self.schedule = schedule
        # The schedule the last async run actually used (record side of the
        # record/replay contract); pass it back as ``schedule`` to replay.
        self.recorded_schedule: AsyncSchedule | None = None
        # High-water mark of adopted-migrant staleness in the last async run
        # (-1 = nothing adopted) — always <= cfg.max_staleness by construction.
        self.last_max_staleness: int | None = None
        # Heterogeneous portfolio mode (DESIGN.md §10): cfg.portfolio names
        # the per-island policies; the single algo_maker is unused.
        if cfg.portfolio:
            if algo_maker is not None:
                raise ValueError(
                    "cfg.portfolio selects per-island policies; pass "
                    "algo_maker=None")
            if cfg.n_islands <= 1:
                raise ValueError(
                    "cfg.portfolio requires n_islands > 1 — each island "
                    "carries one policy")
        elif algo_maker is None:
            raise ValueError("algo_maker is required unless cfg.portfolio is set")
        self.mesh = mesh
        self.mesh_cfg = mesh_cfg
        self.exec_cfg = exec_cfg
        # Island sharding (DESIGN.md §8): a MeshConfig lays the island axis
        # over a 1-D device mesh and the round scan runs under shard_map.
        self._island_mesh = None
        self._axis: str | None = None
        self._n_shards = 1
        if mesh_cfg is not None:
            if mesh is not None:
                raise ValueError(
                    "mesh (population sharding) and mesh_cfg (island "
                    "sharding) are mutually exclusive")
            if cfg.n_islands <= 1:
                raise ValueError("island sharding requires n_islands > 1")
            mesh_cfg.local_islands(cfg.n_islands)   # divisibility check
            self._island_mesh = mesh_cfg.build()
            self._axis = mesh_cfg.axis
            self._n_shards = mesh_cfg.devices
        # Per-objective compiled multi-job runner (see minimize_many). Keyed by
        # objective identity so a scheduler holding one optimizer per bucket
        # reuses the jitted jobs-axis program across flushes.
        self._many_cache: dict[tuple, tuple[Any, Callable]] = {}

    # -- engine ------------------------------------------------------------

    def _evaluator(self, f: Function) -> Callable[[Array], Array]:
        """The engine's batch evaluator for ``f`` — memoized by
        ``make_batch_evaluator``, so every caller (generation steps via
        ``_build``, polish probes via ``_polish``) receives the SAME callable
        and therefore the same compiled xla/pallas path."""
        cfg = self.cfg
        pop_axis_shard = (
            self.mesh is not None and cfg.n_islands == 1 and cfg.pop_axes is not None
        )
        exec_cfg = dataclasses.replace(
            self.exec_cfg, mesh_axis=cfg.pop_axes if pop_axis_shard else None
        )
        return make_batch_evaluator(f, exec_cfg, self.mesh if pop_axis_shard else None)

    def _build(self, f: Function):
        """The per-run policy object: a ``MetaHeuristic`` from ``algo_maker``,
        or a ``core.portfolio.Portfolio`` in heterogeneous mode.

        ``ExecutorConfig.kernel`` is injected as ``kernel_cfg`` into every
        maker that declares the parameter (explicit per-policy params win), so
        one threaded :class:`~repro.kernels.autotune.KernelConfig` reaches the
        fused generation kernels and the pallas eval backend uniformly."""
        cfg = self.cfg
        if cfg.portfolio:
            from repro.core import portfolio as pf  # late: pf imports the algos
            return pf.build_portfolio(
                pf.expand(cfg.portfolio, cfg.n_islands), f=f,
                evaluator=self._evaluator(f), pop=cfg.pop, dim=cfg.dim,
                params=self.params, kernel_cfg=self.exec_cfg.kernel)
        kw = dict(self.params)
        if "kernel_cfg" not in kw and _accepts_kernel_cfg(self.algo_maker):
            kw["kernel_cfg"] = self.exec_cfg.kernel
        return self.algo_maker(
            f=f, evaluator=self._evaluator(f), pop=cfg.pop, dim=cfg.dim, **kw
        )

    def _eval_totals(self, algo) -> tuple[int, int]:
        """(per-generation, init) evaluation totals across all islands — the
        one place homogeneous and heterogeneous accounting meet."""
        if self.cfg.portfolio:
            return algo.per_gen_total, algo.init_total
        return (algo.evals_per_gen * self.cfg.n_islands,
                algo.init_evals * self.cfg.n_islands)

    def _local(self, x: Array) -> Array:
        """This shard's ``n_local``-row block of a replicated per-island table
        (init and generation keys, branch table, schedule rows) under
        ``shard_map`` — the same values the unsharded engine hands to
        ``vmap``, which is the determinism contract of §8. Unsharded (or on
        one shard) the table is already the shard's."""
        if self._axis is None or self._n_shards == 1:
            return x
        n_local = self.cfg.n_islands // self._n_shards
        start = jax.lax.axis_index(self._axis) * n_local
        return jax.lax.dynamic_slice_in_dim(x, start, n_local, axis=0)

    def _round_fn(self, algo) -> Callable[..., State]:
        """One sync round, ``(state, round_key, step_row=None,
        deliver_row=None) -> state``: ``sync_every`` generations, migration
        with the adopted slots' aux re-init, and the incumbent merge.

        Async mode (DESIGN.md §13) takes the schedule rows and differs in
        four places only. The state carries the per-island mailbox leaves
        (``migration.MAILBOX_KEYS``) beside the policy leaves. Islands not in
        ``step_row`` keep their old leaves: the same global key table is
        derived either way, so masked islands never perturb the key
        discipline. Stepping islands post their best-k to their ring
        successor's mailbox, gated by ``deliver_row``, and adopt the newest
        batch at most ``max_staleness`` rounds stale. Per-island round
        counters advance by ``step_row``. With all-ones rows every op reduces
        to the barrier round's values: the ``max_staleness=0`` contract.
        """
        from repro.core import portfolio as pf  # late: pf imports the algos
        cfg = self.cfg
        port = algo if cfg.portfolio else None
        stacked = cfg.n_islands > 1
        axis, n_shards = self._axis, self._n_shards
        local = self._local
        draw = None
        if port is None:
            step = (algo.step_override if algo.step_override is not None
                    else algo.gen)
            draw = algo.draw

        def round_fn(state: State, key: Array, step_row: Array | None = None,
                     deliver_row: Array | None = None) -> State:
            with obs.scope(obs.ROUND):
                return round_body(state, key, step_row, deliver_row)

        def round_body(state: State, key: Array, step_row: Array | None,
                       deliver_row: Array | None) -> State:
            br = None
            if port is not None and port.n_branches > 1:
                br = local(jnp.asarray(port.branch_of))
            if self._async:
                step_row, deliver_row = local(step_row), local(deliver_row)
                box = {k: state[k] for k in mig.MAILBOX_KEYS}
                state = {k: v for k, v in state.items()
                         if k not in mig.MAILBOX_KEYS}

            def island_keys(k: Array) -> Array:
                # Every shard derives the SAME global (I, 2) key table and
                # takes its island block, so per-island key streams match
                # the unsharded engine exactly (determinism contract, §8).
                return local(jax.random.split(k, cfg.n_islands))

            def draw_gen(k: Array) -> Any:
                return jax.vmap(draw)(island_keys(k)) if stacked else draw(k)

            def one_gen(carry: State, xs: tuple[Array, Any]
                        ) -> tuple[State, None]:
                k, drawn = xs
                args = () if drawn is None else (drawn,)
                if not stacked:
                    return step(carry, k, *args), None
                ks = island_keys(k)
                if port is not None:
                    return port.step_stacked(carry, ks, br), None
                return jax.vmap(step)(carry, ks, *args), None

            gen_keys = jax.random.split(key, cfg.sync_every)
            drawn = None if draw is None else jax.vmap(draw_gen)(gen_keys)
            old = state
            state, _ = jax.lax.scan(one_gen, state, (gen_keys, drawn))
            if self._async:
                # The step mask is constant across a tick's generations, so
                # it is applied ONCE after the gens scan, never inside it:
                # the inner scan body stays HLO-identical to the barrier
                # round's, which is what makes the max_staleness=0
                # degradation bit-exact (a select inside the loop body
                # changes XLA fusion of the policy arithmetic and drifts pso
                # by ulps). The select is pure data movement.
                state = jax.tree.map(
                    lambda a, b: jnp.where(
                        step_row.reshape(step_row.shape + (1,) * (a.ndim - 1)),
                        a, b),
                    state, old)

            if stacked and cfg.migration != "none":
                old_pop, old_fit = state["pop"], state["fit"]
                if not self._async:
                    if port is None:
                        mig_alive = state.get("alive")
                    else:
                        # Per-island liveness for the (global) starvation
                        # count: policies that own an aging mask (ga)
                        # contribute it; the rest contribute isfinite(fit) —
                        # exactly what the plain engine's alive=None default
                        # computes, so a homogeneous portfolio stays
                        # bit-identical even when the executor has evicted
                        # candidates to +inf.
                        oa = local(jnp.asarray(port.owns_alive))
                        mig_alive = jnp.where(oa[:, None], state["alive"],
                                              jnp.isfinite(state["fit"]))
                with obs.scope(obs.MIGRATE):
                    if self._async:
                        box = mig.mailbox_post(
                            box, old_pop, old_fit, cfg.n_migrants,
                            step_row & deliver_row, axis=axis,
                            n_shards=n_shards)
                        pop, fit, box = mig.mailbox_adopt(
                            box, old_pop, old_fit, cfg.max_staleness,
                            step_row)
                    else:
                        pop, fit = mig.migrate(
                            cfg.migration, old_pop, old_fit,
                            k=cfg.n_migrants, alive=mig_alive,
                            axis=axis, n_shards=n_shards,
                        )
                    state = {**state, "pop": pop, "fit": fit}
                    if port is not None or pf.has_adopt_state(algo.name):
                        # Migration carries pos/fit only; slots whose values
                        # changed hold adopted migrants. They revive (alive)
                        # and the destination policy re-initializes its aux
                        # slots (velocity, pbest, age, ... — DESIGN.md §10).
                        # The plain engine applies the same registered adopt
                        # rules to the native state, so homogeneous
                        # portfolios stay bit-identical to it for EVERY
                        # policy — and plain ga/pso no longer re-kill or
                        # mislead the migrants they adopt.
                        adopted = (jnp.any(pop != old_pop, axis=-1)
                                   | (fit != old_fit))
                        if port is not None:
                            state = port.adopt_stacked(state, adopted, br)
                        else:
                            state = jax.vmap(
                                partial(pf.adopt_native, algo.name))(
                                    state, adopted)

            if stacked and cfg.share_incumbent:
                bv, ba = state["best_val"], state["best_arg"]
                if axis is not None and n_shards > 1:
                    # Device-side Observer across shards: gather every
                    # island's incumbent, broadcast the global best back.
                    with obs.scope(obs.SYNC):
                        gbv = jax.lax.all_gather(bv, axis, tiled=True)
                        gba = jax.lax.all_gather(ba, axis, tiled=True)
                else:
                    gbv, gba = bv, ba
                gi = jnp.argmin(gbv)
                state = {
                    **state,
                    "best_val": jnp.full_like(bv, gbv[gi]),
                    "best_arg": jnp.broadcast_to(gba[gi], ba.shape),
                }

            if self._async:
                box = {**box, "round_ctr":
                       box["round_ctr"] + step_row.astype(jnp.int32)}
                state = {**state, **box}
            return state

        return round_fn

    def _materialize_schedule(self, n_rounds: int
                              ) -> tuple[Array, Array]:
        """Concrete (step, deliver) masks for an async run, recording them in
        ``recorded_schedule`` — the record half of record/replay."""
        sched = self.schedule if self.schedule is not None else AsyncSchedule()
        step, deliver = sched.materialize(n_rounds, self.cfg.n_islands)
        self.recorded_schedule = AsyncSchedule(
            step=step, deliver=deliver, seed=sched.seed)
        return jnp.asarray(step), jnp.asarray(deliver)

    def _polish(self, f: Function) -> tuple[Callable[[State], State] | None, int]:
        """(state -> state polish pass, evals per polished point) — the hybrid
        memetic layer (DESIGN.md §6), or ``(None, 0)`` when ``cfg.polish`` is
        off. The pass takes each island's ``polish_topk`` best candidates
        through a fixed-shape batched local descent (``optim.descent
        .make_polish``) and writes improvements back into the population and
        the incumbent. It reuses the SAME cached evaluator as the generation
        steps (``make_batch_evaluator`` memoizes on objective + config), so
        polish probes hit the identical xla/pallas backend. Deterministic —
        no RNG — so it cannot perturb the engine's key chain.
        """
        cfg = self.cfg
        if cfg.polish == "none":
            return None, 0
        from repro.optim import descent  # late: optim.descent imports core.api

        pcfg = descent.PolishConfig(method=cfg.polish, steps=cfg.polish_steps)
        polish = descent.make_polish(f, self._evaluator(f), cfg.dim, pcfg)
        k = min(cfg.polish_topk, cfg.pop)

        def polish_island(state: State) -> State:
            with obs.scope(obs.POLISH):
                pop, fit = state["pop"], state["fit"]
                _, idx = jax.lax.top_k(-fit, k)    # k best (smallest) fitness
                xs, fs = pop[idx], fit[idx]
                xs2, fs2 = polish(xs, fs)
                better = fs2 < fs                  # polish is monotone; guard anyway
                pop = pop.at[idx].set(jnp.where(better[:, None], xs2, xs))
                fit = fit.at[idx].set(jnp.where(better, fs2, fs))
                return track_best(state, pop, fit)

        pass_fn = jax.vmap(polish_island) if cfg.n_islands > 1 else polish_island
        return pass_fn, descent.polish_evals_per_point(cfg.dim, pcfg)

    def _scan_rounds(
        self, algo, polish_pass: Callable[[State], State] | None,
    ) -> Callable[..., tuple[State, Array]]:
        """Per-shard round scan ``(state, round_keys[, step, deliver]) ->
        (state, history)`` — the body both the unsharded run and the
        ``shard_map``-wrapped sharded run execute (polish on its cadence,
        per-round incumbent history). Async mode passes the schedule masks,
        which join the scan's per-round inputs, so one compiled program
        serves every schedule."""
        cfg = self.cfg
        stacked = cfg.n_islands > 1
        every = max(1, cfg.polish_every)
        axis, n_shards = self._axis, self._n_shards
        round_fn = self._round_fn(algo)

        def scan_rounds(state: State, round_keys: Array,
                        *masks: Array) -> tuple[State, Array]:
            def body(carry: State, xs: tuple) -> tuple[State, Array]:
                rk, r, *rows = xs
                carry = round_fn(carry, rk, *rows)
                if polish_pass is not None:
                    carry = jax.lax.cond(
                        (r + 1) % every == 0, polish_pass, lambda s: s, carry)
                bv = carry["best_val"]
                point = jnp.min(bv) if stacked else bv
                if axis is not None and n_shards > 1:
                    with obs.scope(obs.SYNC):
                        point = jax.lax.pmin(point, axis)   # exact: min of mins
                return carry, point

            rs = jnp.arange(round_keys.shape[0])
            return jax.lax.scan(body, state, (round_keys, rs, *masks))

        return scan_rounds

    def _run_fn(
        self, algo, polish_pass: Callable[[State], State] | None = None,
    ) -> Callable[..., tuple]:
        """Whole-run device program ``(state, round_keys[, step, deliver]) ->
        (best_arg, best_val, history[, stale])``: scan over sync rounds
        (polishing on the ``polish_every`` cadence) and select the global
        incumbent on device. With an island mesh the scan runs under
        ``shard_map`` (one shard per island block) and the final selection
        happens on the reassembled global state. Async mode takes the
        schedule masks and also returns the adopted-staleness high-water
        mark."""
        stacked = self.cfg.n_islands > 1
        body = self._scan_rounds(algo, polish_pass)
        if self._island_mesh is not None:
            in_specs, out_specs = mesh_mod.island_specs(
                self._axis, 3 if self._async else 1)
            body = mesh_mod.shard_map(
                body, self._island_mesh,
                in_specs=in_specs, out_specs=out_specs)

        def run(state: State, round_keys: Array, *masks: Array) -> tuple:
            state, history = body(state, round_keys, *masks)
            arg, val = _select_best(state, stacked)
            if self._async:
                return arg, val, history, jnp.max(state["stale_seen"])
            return arg, val, history

        return run

    def _shard_state(self, state: State) -> State:
        if self._island_mesh is not None:
            spec = P(self._axis)
            return jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(self._island_mesh, spec)), state)
        if self.mesh is None or self.cfg.n_islands <= 1:
            return state
        axes = self.cfg.island_axes

        def put(x: Array) -> Array:
            spec = P(axes, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree.map(put, state)

    def _init_state(self, algo, ik: Array, local: bool = False) -> State:
        """Fresh engine state from init key ``ik`` — the one init rule every
        path (minimize, jobs axis, host stepper) shares. ``local`` builds
        only this shard's islands under ``shard_map``: its rows of the global
        init-key and branch tables, and an ``n_local``-island mailbox. Async
        mode merges the mailbox leaves (``migration.mailbox_init``) into the
        state dict, so checkpointing and sharding see one pytree."""
        cfg = self.cfg
        rows = self._local if local else (lambda x: x)
        if cfg.n_islands == 1:
            state = algo.init(ik)
        else:
            iks = rows(jax.random.split(ik, cfg.n_islands))
            if cfg.portfolio:
                br = (rows(jnp.asarray(algo.branch_of))
                      if algo.n_branches > 1 else None)
                state = algo.init_stacked(iks, br)
            else:
                state = jax.vmap(algo.init)(iks)
        if self._async:
            n = cfg.n_islands // self._n_shards if local else cfg.n_islands
            state = {**state, **mig.mailbox_init(
                n, cfg.mailbox_slots, cfg.n_migrants, cfg.dim)}
        return state

    def _warm_fn(self, f: Function, algo) -> Callable[[State, Array, Array], State]:
        """``(state, warm (W, dim), warm_fit (W,)) -> state`` — immigration at
        init, the cross-host federation hop (``launch/federate.py``,
        DESIGN.md §13): adopt externally-routed candidates into island 0's
        worst slots through the same worst-k replacement rule migration uses,
        re-initializing destination-policy aux slots and refreshing the
        incumbent. Deterministic, so warm-started runs stay reproducible."""
        from repro.core import portfolio as pf  # late: pf imports the algos
        cfg = self.cfg
        port = algo if cfg.portfolio else None
        stacked = cfg.n_islands > 1

        def inject(state: State, w: Array, wf: Array) -> State:
            if stacked:
                old_pop, old_fit = state["pop"][0], state["fit"][0]
                pop0, fit0 = mig._replace_worst(old_pop, old_fit, w, wf)
                pop = state["pop"].at[0].set(pop0)
                fit = state["fit"].at[0].set(fit0)
                state = {**state, "pop": pop, "fit": fit}
                if port is not None or pf.has_adopt_state(algo.name):
                    changed = (jnp.any(pop0 != old_pop, axis=-1)
                               | (fit0 != old_fit))
                    adopted = (jnp.zeros(fit.shape, bool).at[0].set(changed))
                    if port is not None:
                        br = (jnp.asarray(port.branch_of)
                              if port.n_branches > 1 else None)
                        state = port.adopt_stacked(state, adopted, br)
                    else:
                        state = jax.vmap(partial(pf.adopt_native, algo.name))(
                            state, adopted)
                i = jnp.argmin(fit0)
                better = fit0[i] < state["best_val"][0]
                bv = state["best_val"].at[0].set(
                    jnp.where(better, fit0[i], state["best_val"][0]))
                ba = state["best_arg"].at[0].set(
                    jnp.where(better, pop0[i], state["best_arg"][0]))
                return {**state, "best_val": bv, "best_arg": ba}
            old_pop, old_fit = state["pop"], state["fit"]
            pop, fit = mig._replace_worst(old_pop, old_fit, w, wf)
            state = {**state, "pop": pop, "fit": fit}
            if pf.has_adopt_state(algo.name):
                changed = (jnp.any(pop != old_pop, axis=-1) | (fit != old_fit))
                state = pf.adopt_native(algo.name, state, changed)
            return track_best(state, pop, fit)

        return inject

    def _inject_warm(self, f: Function, algo, state: State, warm) -> State:
        """Host-side warm-start: evaluate the candidates with the run's own
        evaluator (same compiled backend as generation steps) and adopt them
        into the freshly-initialized state. Runs before sharding."""
        w = jnp.asarray(warm, jnp.float32)
        if w.ndim != 2 or w.shape[1] != self.cfg.dim:
            raise ValueError(
                f"warm candidates must have shape (W, {self.cfg.dim}), "
                f"got {w.shape}")
        wf = self._evaluator(f)(w)
        return self._warm_fn(f, algo)(state, w, wf)

    def _budget(self, per_gen_total: int, init_total: int,
                polish_per_point: int = 0) -> tuple[int, int, int, int]:
        """(n_rounds, per_round_evals, n_polish, per_polish_evals) from the
        eval budget — one accounting rule shared by minimize and
        minimize_many, fed by ``_eval_totals`` so heterogeneous portfolios
        (per-island ``evals_per_gen``) charge exactly what each island's
        policy consumes. Polish events fire every ``polish_every`` rounds and
        cost ``polish_topk * polish_per_point`` per island, charged against
        the same ``max_evals`` as generation steps, so hybrid runs stay
        budget-comparable with plain ones."""
        cfg = self.cfg
        per_round = per_gen_total * cfg.sync_every
        budget = cfg.max_evals - init_total
        if polish_per_point <= 0 or cfg.polish == "none":
            return max(1, budget // max(per_round, 1)), per_round, 0, 0
        # top-k is clamped to the island population in _polish; charge the same
        per_polish = polish_per_point * min(cfg.polish_topk, cfg.pop) * cfg.n_islands
        every = max(1, cfg.polish_every)

        def cost(n: int) -> int:
            return n * per_round + (n // every) * per_polish

        lo, hi = 1, max(1, budget // max(per_round, 1))
        while lo < hi:                      # largest n_rounds with cost <= budget
            mid = (lo + hi + 1) // 2
            if cost(mid) <= budget:
                lo = mid
            else:
                hi = mid - 1
        return lo, per_round, lo // every, per_polish

    def _single_fn(self, f: Function) -> tuple[Any, Callable, int]:
        """Cached (algo, jitted device-resident run, polish evals/point) for
        ``f`` — repeated ``minimize`` calls on one optimizer reuse the
        compiled program instead of re-tracing a fresh closure every call.
        Keyed by ``Function.cache_token()`` — a GC-stable identity token, so
        a recycled ``id()`` can never silently serve a stale program."""
        ck = ("single", *f.cache_token())
        hit = self._many_cache.get(ck)
        if hit is not None and hit[0] is f.fn:
            return hit[1], hit[2], hit[3]
        algo = self._build(f)
        polish_pass, pp = self._polish(f)
        run = jax.jit(self._run_fn(algo, polish_pass), donate_argnums=0)
        self._many_cache[ck] = (f.fn, algo, run, pp)
        return algo, run, pp

    def minimize(self, f: Function, key: Array,
                 warm: Any = None) -> OptimizeResult:
        """Run the full eval budget on objective ``f`` from PRNG ``key`` as
        one device-resident program: one jitted scan, one host transfer at
        the end. :class:`BucketStepper` steps the same rounds from the host
        when a caller needs round granularity.

        ``warm`` (optional, (W, dim)) are externally-routed immigrants —
        federation migrants — adopted into the initial population before
        round 0 (see :meth:`_warm_fn`).
        """
        cfg = self.cfg
        algo, run, pp = self._single_fn(f)
        per_gen_total, init_total = self._eval_totals(algo)
        n_rounds, per_round, n_polish, per_polish = self._budget(
            per_gen_total, init_total, pp)

        with obs.span(obs.ENGINE_INIT):
            key, ik = jax.random.split(key)
            state = self._init_state(algo, ik)
            if warm is not None and len(warm):
                state = self._inject_warm(f, algo, state, warm)
            state = self._shard_state(state)
            round_keys = _chain_split(key, n_rounds)
            masks = self._materialize_schedule(n_rounds) if self._async else ()

        arg, val, history = self._dispatch(run, state, round_keys, *masks)
        n_evals = (init_total + n_rounds * per_round + n_polish * per_polish)
        return OptimizeResult(
            arg=arg, value=float(val), n_evals=n_evals,
            n_gens=n_rounds * cfg.sync_every, history=history,
        )

    def _dispatch(self, run: Callable, *args: Any) -> list:
        """Call a resident program once and fetch its outputs in one host
        transfer. The async program's last output, the adopted-staleness
        high-water mark, goes to ``last_max_staleness``."""
        with self.mesh if self.mesh is not None else contextlib.nullcontext():
            with obs.span(obs.ENGINE_DISPATCH):
                out = run(*args)
            with obs.span(obs.ENGINE_FETCH):
                out = list(jax.device_get(out))
        if self._async:
            self.last_max_staleness = int(np.max(out.pop()))
        return out

    def bucket_stepper(self, f: Function) -> "BucketStepper":
        """Cached host-stepped jobs-axis runner for objective ``f`` — the
        round-granular sibling of :meth:`minimize_many` (see
        :class:`BucketStepper`). Requires the unsharded engine: the
        host-stepped loop cannot run inside ``shard_map`` (DESIGN.md §8)."""
        ck = ("stepper", *f.cache_token())
        hit = self._many_cache.get(ck)
        if hit is not None and hit[0] is f.fn:
            return hit[1]
        stepper = BucketStepper(self, f)
        self._many_cache[ck] = (f.fn, stepper)
        return stepper

    # -- jobs axis ---------------------------------------------------------

    def _many_fn(self, f: Function) -> tuple[MetaHeuristic, Callable, int]:
        """Compiled jobs-axis runner for objective ``f``: ``keys (J, 2)[,
        step, deliver] -> (args (J, dim), vals (J,), histories (J,
        n_rounds)[, stale])``, plus the polish evals/point for budget
        accounting.

        Each job replays ``minimize``'s exact device program — the same
        ``split``/``_chain_split`` key discipline, init, round scan and
        incumbent selection — so a job's trajectory is bit-identical to a
        standalone ``minimize`` call with the same key. ``vmap`` over jobs
        composes outside the per-island ``vmap`` and the executor's
        ``shard_map``: J same-shaped jobs cost one dispatch instead of J.
        Async jobs share one (replicated) schedule; the masks are data, so
        one compiled program serves every schedule of this length.

        With an island mesh the jobs-axis ``vmap`` moves *inside* the
        ``shard_map``: every shard initializes and steps its own island block
        for all J jobs, and only the final selection runs on the reassembled
        global state — the sharded analogue of the same program.
        """
        ck = ("many", *f.cache_token())
        hit = self._many_cache.get(ck)
        if hit is not None and hit[0] is f.fn:
            return hit[1], hit[2], hit[3]

        algo = self._build(f)
        polish_pass, pp = self._polish(f)
        n_rounds, _, _, _ = self._budget(*self._eval_totals(algo), pp)
        in_axes = (0, None, None) if self._async else (0,)

        if self._island_mesh is None:
            run = self._run_fn(algo, polish_pass)

            def one_job(k: Array, *masks: Array) -> tuple:
                key, ik = jax.random.split(k)
                return run(self._init_state(algo, ik),
                           _chain_split(key, n_rounds), *masks)

            many = jax.jit(jax.vmap(one_job, in_axes=in_axes))
        else:
            scan_rounds = self._scan_rounds(algo, polish_pass)

            def one_job_local(k: Array, *masks: Array) -> tuple[State, Array]:
                key, ik = jax.random.split(k)
                return scan_rounds(self._init_state(algo, ik, local=True),
                                   _chain_split(key, n_rounds), *masks)

            sharded = mesh_mod.shard_map(
                jax.vmap(one_job_local, in_axes=in_axes), self._island_mesh,
                in_specs=(P(),) * len(in_axes),
                out_specs=(P(None, self._axis), P()))

            def many_sharded(keys: Array, *masks: Array) -> tuple:
                state, hists = sharded(keys, *masks)   # (J, I, ...), (J, R)
                args, vals = jax.vmap(lambda s: _select_best(s, True))(state)
                if self._async:
                    return args, vals, hists, jnp.max(state["stale_seen"])
                return args, vals, hists

            many = jax.jit(many_sharded)
        self._many_cache[ck] = (f.fn, algo, many, pp)
        return algo, many, pp

    def minimize_many(self, f: Function, keys: Array) -> list[OptimizeResult]:
        """Run one job per row of ``keys (J, 2)`` in a single jitted dispatch.

        The scheduler's bucket-execution primitive: all jobs share this
        optimizer's config (one shape-class), differing only by PRNG key.
        When a mesh is attached the jobs axis is sharded over
        ``cfg.island_axes`` — the multi-job analogue of island sharding.
        """
        cfg = self.cfg
        algo, many, pp = self._many_fn(f)
        per_gen_total, init_total = self._eval_totals(algo)
        n_rounds, per_round, n_polish, per_polish = self._budget(
            per_gen_total, init_total, pp)

        keys = jnp.asarray(keys)
        n_jobs = keys.shape[0]
        if self.mesh is not None:
            # Bucket sizes are arbitrary (the service flushes whatever the
            # deadline window collected): pad the jobs axis to a multiple of
            # the sharding axis and slice the extras back off below.
            n_dev = 1
            for a in cfg.island_axes:
                n_dev *= self.mesh.shape[a]
            pad = (-n_jobs) % n_dev
            if pad:
                keys = jnp.concatenate(
                    [keys, jnp.broadcast_to(keys[:1], (pad, *keys.shape[1:]))])
            keys = jax.device_put(
                keys, NamedSharding(self.mesh, P(cfg.island_axes, None)))
        masks = self._materialize_schedule(n_rounds) if self._async else ()
        args, vals, hists = self._dispatch(many, keys, *masks)

        n_evals = (init_total + n_rounds * per_round + n_polish * per_polish)
        return [
            OptimizeResult(
                arg=args[j], value=float(vals[j]), n_evals=n_evals,
                n_gens=n_rounds * cfg.sync_every, history=hists[j],
            )
            for j in range(n_jobs)
        ]


class BucketStepper:
    """Host-stepped jobs-axis runner — ``minimize_many``'s exact per-round
    program, advanced one sync round at a time from the host (DESIGN.md §12).
    It is the engine's only host-stepped runner: ``minimize`` and
    ``minimize_many`` are device-resident, and a caller that needs round
    granularity steps a bucket of one or more jobs through this class.

    The service layer's hardening primitive: because control returns to the
    host at every round boundary, a bucket run can stream per-round incumbent
    progress to pollers, honor cooperative cancellation, and snapshot its
    full engine state through ``checkpoint/store.py`` — while staying
    **bit-identical** to the device-resident ``minimize_many`` scan (same
    init, same ``_chain_split`` key streams, same round/polish/history order;
    the contract ``tests/test_service.py`` enforces).

    Requires the unsharded engine (no island mesh, no population mesh): the
    host-stepped loop cannot run inside ``shard_map``. Portfolio buckets are
    also refused: XLA compiles the ``lax.switch`` round body slightly
    differently per-round than inside the resident scan (last-ulp float
    drift), which would break the bit-identity contract — so the scheduler
    keeps those buckets on the device-resident path.
    """

    def __init__(self, opt: IslandOptimizer, f: Function) -> None:
        if opt._island_mesh is not None or opt.mesh is not None:
            raise ValueError(
                "bucket_stepper requires the unsharded engine — the "
                "host-stepped loop cannot run inside shard_map (DESIGN.md §8)")
        if opt.cfg.portfolio:
            raise ValueError(
                "bucket_stepper does not support portfolio islands: the "
                "per-round jit of the lax.switch body is not bit-identical "
                "to the resident scan's compilation of it (DESIGN.md §12)")
        cfg = opt.cfg
        self.cfg = cfg
        algo = opt._build(f)
        polish_pass, pp = opt._polish(f)
        per_gen_total, init_total = opt._eval_totals(algo)
        self.n_rounds, self.per_round, _, self.per_polish = opt._budget(
            per_gen_total, init_total, pp)
        self.init_evals = init_total
        self.every = max(1, cfg.polish_every)
        self.has_polish = polish_pass is not None
        stacked = cfg.n_islands > 1
        n_rounds = self.n_rounds
        round_fn = opt._round_fn(algo)
        # Async buckets step under the optimizer's schedule: all-ones masks
        # (the barrier cadence, what scheduler-driven buckets run) unless an
        # AsyncSchedule was given. The resident async program computes the
        # same values under the same masks, so the stepped-vs-resident
        # bit-identity contract (DESIGN.md §12) extends to async buckets.
        self._masks = (tuple(np.asarray(m) for m in
                             opt._materialize_schedule(n_rounds))
                       if opt._async else None)
        in_axes = (0, 0, None, None) if opt._async else (0, 0)
        # Warm-start immigration (launch/federate.py): jitted lazily on the
        # first bucket that actually carries warm candidates.
        self._warm_fn = opt._warm_fn(f, algo)
        self._warm_eval = opt._evaluator(f)
        self._inject_jit: Callable | None = None

        def prep(k: Array) -> tuple[State, Array]:
            # minimize_many's one_job preamble, verbatim: the same split/init/
            # _chain_split discipline, so trajectories match bit-for-bit.
            key, ik = jax.random.split(k)
            return opt._init_state(algo, ik), _chain_split(key, n_rounds)

        def keys_only(k: Array) -> Array:
            key, _ = jax.random.split(k)
            return _chain_split(key, n_rounds)

        def point(state: State) -> Array:
            bv = state["best_val"]
            return jnp.min(bv, axis=-1) if stacked else bv

        def step(state: State, rk: Array, *rows: Array) -> tuple[State, Array]:
            state = jax.vmap(round_fn, in_axes=in_axes)(state, rk, *rows)
            return state, point(state)

        def step_polish(state: State, rk: Array,
                        *rows: Array) -> tuple[State, Array]:
            # Polish BEFORE the history point is read — the device-resident
            # scan body's order (round_fn -> cond polish -> point).
            state = jax.vmap(round_fn, in_axes=in_axes)(state, rk, *rows)
            state = jax.vmap(polish_pass)(state)
            return state, point(state)

        self._prep = jax.jit(jax.vmap(prep))
        self._keys = jax.jit(jax.vmap(keys_only))
        self._best = jax.jit(jax.vmap(lambda s: _select_best(s, stacked)))
        self._step = jax.jit(step, donate_argnums=0)
        self._step_polish = (jax.jit(step_polish, donate_argnums=0)
                             if self.has_polish else None)

    def init(self, keys: Array) -> tuple[State, Array]:
        """``keys (J, 2) -> (job-stacked state, round keys (J, n_rounds, 2))``
        — one jitted dispatch, identical to ``minimize_many``'s per-job init."""
        return self._prep(keys)

    def inject(self, state: State, warm) -> State:
        """Adopt warm-start immigrants (federation migrants, ``OptRequest
        .warm``) into every job's freshly-initialized state — the jobs-axis
        form of ``IslandOptimizer._warm_fn``. All jobs in a bucket share one
        warm batch (it is part of the shape-class), so the candidates are
        evaluated once and the adoption vmaps over jobs. Donates ``state``."""
        w = jnp.asarray(warm, jnp.float32)
        if w.ndim != 2 or w.shape[1] != self.cfg.dim:
            raise ValueError(
                f"warm candidates must have shape (W, {self.cfg.dim}), "
                f"got {w.shape}")
        if self._inject_jit is None:
            self._inject_jit = jax.jit(
                jax.vmap(self._warm_fn, in_axes=(0, None, None)),
                donate_argnums=0)
        return self._inject_jit(state, w, self._warm_eval(w))

    def round_keys(self, keys: Array) -> Array:
        """Re-derive the ``(J, n_rounds, 2)`` round-key table from job keys
        without re-running init — how a resumed run (which restores its state
        from a checkpoint) rebuilds the exact key stream it was killed on."""
        return self._keys(keys)

    def state_shape(self, keys: Array) -> State:
        """``ShapeDtypeStruct`` pytree of the job-stacked state — the
        ``like`` template a checkpoint restore validates shapes against."""
        return jax.eval_shape(lambda k: self._prep(k)[0], keys)

    def step(self, state: State, round_keys: Array, r: int) -> tuple[State, Array]:
        """Advance round ``r``: ``sync_every`` generations + migration +
        incumbent merge (+ polish on its cadence), returning the new state and
        each job's current global best value ``(J,)``. Donates ``state`` —
        callers must not reuse the argument after the call."""
        fn = (self._step_polish
              if self.has_polish and (r + 1) % self.every == 0 else self._step)
        rows = () if self._masks is None else (self._masks[0][r],
                                               self._masks[1][r])
        with obs.span(obs.ENGINE_STEP):
            return fn(state, round_keys[:, r], *rows)

    def best(self, state: State) -> tuple[Array, Array]:
        """Per-job global incumbent ``(args (J, dim), vals (J,))`` from the
        current state — non-donating, usable mid-run for partial results."""
        return self._best(state)

    def evals_done(self, rounds: int) -> int:
        """Per-job evaluations consumed after ``rounds`` completed rounds —
        the same accounting rule ``minimize_many`` charges at full budget."""
        n_polish = rounds // self.every if self.has_polish else 0
        return self.init_evals + rounds * self.per_round + n_polish * self.per_polish


def _select_best(state: State, stacked: bool) -> tuple[Array, Array]:
    """Global incumbent from (possibly island-stacked) engine state — the one
    selection rule shared by the device-resident and host-stepped paths."""
    bv = state["best_val"]
    if stacked:
        gi = jnp.argmin(bv)
        return state["best_arg"][gi], bv[gi]
    return state["best_arg"], bv


@partial(jax.jit, static_argnums=1)
def _chain_split(key: Array, n: int) -> Array:
    """(n, 2) round keys from the sequential ``key, rk = split(key)`` chain —
    the same stream the engine's original host round loop drew, so trajectories
    are reproducible across the host-stepped and device-resident paths."""

    def body(k: Array, _: None) -> tuple[Array, Array]:
        ks = jax.random.split(k)
        return ks[0], ks[1]

    _, rks = jax.lax.scan(body, key, None, length=n)
    return rks


def uniform_init(key: Array, pop: int, dim: int, lo: float, hi: float) -> Array:
    """Uniform-random (pop, dim) population in the box — the shared init."""
    return jax.random.uniform(key, (pop, dim), minval=lo, maxval=hi, dtype=jnp.float32)


def clip_box(x: Array, lo: float, hi: float) -> Array:
    """Project candidates back into the box domain (the paper's constraint)."""
    return jnp.clip(x, lo, hi)


def track_best(state: State, pop: Array, fit: Array) -> State:
    """Update the per-island incumbent from the current population."""
    i = jnp.argmin(fit)
    better = fit[i] < state["best_val"]
    return {
        **state,
        "pop": pop,
        "fit": fit,
        "best_val": jnp.where(better, fit[i], state["best_val"]),
        "best_arg": jnp.where(better, pop[i], state["best_arg"]),
    }
