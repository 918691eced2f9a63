"""Island-engine + meta-heuristic behaviour tests (the paper's §IV semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ALGORITHMS, IslandConfig, IslandOptimizer
from repro.core import migration
from repro.functions import get

KEY = jax.random.PRNGKey(3)
SPHERE = get("sphere")


@pytest.mark.parametrize("algo", sorted(ALGORITHMS))
def test_improves_over_random(algo):
    cfg = IslandConfig(n_islands=2, pop=24, dim=6, sync_every=5,
                       max_evals=6000,
                       migration="starvation" if algo in ("ga", "bh") else "ring")
    res = IslandOptimizer(ALGORITHMS[algo], cfg).minimize(SPHERE, KEY)
    # random uniform in [-100,100]^6 has E[f] = 6 * (200^2/12) = 20000
    assert res.value < 5000, (algo, res.value)
    assert res.n_evals <= cfg.max_evals
    assert np.isfinite(res.value)


def test_budget_respected():
    for budget in (2000, 10_000):
        cfg = IslandConfig(n_islands=1, pop=32, dim=4, migration="none",
                           max_evals=budget)
        res = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
        assert res.n_evals <= budget


def test_de_sync_deterministic():
    cfg = IslandConfig(n_islands=2, pop=16, dim=4, max_evals=4000)
    r1 = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
    r2 = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
    assert r1.value == r2.value                      # same seed, same result


def test_de_chunked_mode_differs_but_works():
    """The 'non-determinism-ok' flag changes the trajectory (stale reads) but
    is itself reproducible in SPMD."""
    cfg = IslandConfig(n_islands=1, pop=32, dim=6, migration="none",
                       max_evals=8000)
    rs = IslandOptimizer(ALGORITHMS["de"], cfg,
                         params={"barrier_mode": "sync"}).minimize(SPHERE, KEY)
    rc = IslandOptimizer(ALGORITHMS["de"], cfg,
                         params={"barrier_mode": "chunked"}).minimize(SPHERE, KEY)
    rc2 = IslandOptimizer(ALGORITHMS["de"], cfg,
                          params={"barrier_mode": "chunked"}).minimize(SPHERE, KEY)
    assert rc.value == rc2.value
    assert np.isfinite(rs.value) and np.isfinite(rc.value)


def test_best1bin_strategy():
    cfg = IslandConfig(n_islands=1, pop=32, dim=6, migration="none",
                       max_evals=8000)
    r = IslandOptimizer(ALGORITHMS["de"], cfg,
                        params={"strategy": "best1bin"}).minimize(SPHERE, KEY)
    assert r.value < 100.0


# --- migration unit semantics ------------------------------------------------

def test_ring_migration_improves_receiver():
    I, P, D = 4, 8, 3
    pop = jax.random.uniform(KEY, (I, P, D), minval=-1, maxval=1)
    fit = jnp.arange(I * P, dtype=jnp.float32).reshape(I, P)  # island0 best
    new_pop, new_fit = migration.ring(pop, fit, k=2)
    # every island's best fitness can only improve or stay
    assert bool(jnp.all(new_fit.min(axis=1) <= fit.min(axis=1)))
    # island 1 receives island 0's two best
    assert float(new_fit[1].min()) <= float(fit[0].min())
    assert new_pop.shape == pop.shape


def test_ring_migration_conserves_capacity():
    I, P, D = 3, 10, 4
    pop = jax.random.uniform(KEY, (I, P, D))
    fit = jax.random.uniform(jax.random.fold_in(KEY, 1), (I, P))
    new_pop, new_fit = migration.ring(pop, fit, k=2)
    assert new_pop.shape == (I, P, D) and new_fit.shape == (I, P)


def test_starvation_routes_to_weakest():
    I, P, D = 4, 6, 2
    pop = jnp.zeros((I, P, D))
    fit = jnp.full((I, P), 10.0)
    alive = jnp.ones((I, P), bool)
    # island 2 is starving: only 1 live member (others have inf slots)
    fit = fit.at[2, 1:].set(jnp.inf)
    alive = alive.at[2, 1:].set(False)
    fit = fit.at[0, 0].set(1.0)                      # island 0 holds the best
    pop = pop.at[0, 0].set(jnp.array([5.0, 5.0]))
    new_pop, new_fit = migration.starvation(pop, fit, k=2, alive=alive)
    assert float(new_fit[2].min()) == 1.0            # best migrated to host
    assert bool(jnp.all(new_fit[1] == fit[1]))       # non-host islands untouched


def test_ring_adopts_only_better_migrants():
    """A migrant worse than the receiver's resident worst is rejected."""
    I, P, D = 3, 8, 2
    pop = jax.random.uniform(KEY, (I, P, D))
    # island 0's best (the migrants island 1 receives) are all worse than
    # island 1's worst resident -> island 1 must be untouched
    fit = jnp.stack([
        jnp.full((P,), 100.0),                       # donor to island 1
        jnp.arange(P, dtype=jnp.float32),            # receiver, all < 100
        jnp.full((P,), 50.0),
    ])
    new_pop, new_fit = migration.ring(pop, fit, k=2)
    assert bool(jnp.all(new_fit[1] == fit[1]))
    assert bool(jnp.all(new_pop[1] == pop[1]))
    # island 2 (worst resident 50) does adopt island 1's best (0.0)
    assert float(new_fit[2].min()) == 0.0


def test_starvation_picks_emptiest_host():
    """The island with the fewest live members hosts the immigration."""
    I, P, D = 3, 6, 2
    pop = jnp.zeros((I, P, D))
    fit = jnp.full((I, P), 10.0)
    alive = jnp.ones((I, P), bool)
    # live counts: island0 = 6, island1 = 1, island2 = 4  -> host must be 1
    fit = fit.at[1, 1:].set(jnp.inf)
    alive = alive.at[1, 1:].set(False)
    fit = fit.at[2, 4:].set(jnp.inf)
    alive = alive.at[2, 4:].set(False)
    fit = fit.at[0, 0].set(1.0)
    new_pop, new_fit = migration.starvation(pop, fit, k=2, alive=alive)
    assert float(new_fit[1].min()) == 1.0            # arrived at island 1
    assert bool(jnp.all(new_fit[0] == fit[0]))       # donors untouched
    assert bool(jnp.all(new_fit[2] == fit[2]))


def test_starvation_clamps_migrants_to_paper_limit():
    """At most k<=2 individuals leave an island per round, even if k > 2."""
    I, P, D = 3, 8, 2
    pop = jnp.zeros((I, P, D))
    # distinct per-donor fitness bands so arrivals are attributable
    fit = jnp.stack([
        jnp.arange(P, dtype=jnp.float32),            # donor 0: 0..7
        jnp.arange(P, dtype=jnp.float32) + 10.0,     # donor 1: 10..17
        jnp.full((P,), jnp.inf),                     # host: starving (0 alive)
    ])
    new_pop, new_fit = migration.starvation(pop, fit, k=5)
    from_donor0 = int(jnp.sum(new_fit[2] < 10.0))
    from_donor1 = int(jnp.sum((new_fit[2] >= 10.0) & (new_fit[2] < 20.0)))
    assert from_donor0 <= 2 and from_donor1 <= 2, (from_donor0, from_donor1)
    assert from_donor0 == 2                          # the best two did arrive
    assert float(new_fit[2].min()) == 0.0


def test_no_migration_single_island():
    pop = jax.random.uniform(KEY, (1, 8, 3))
    fit = jax.random.uniform(jax.random.fold_in(KEY, 2), (1, 8))
    p2, f2 = migration.ring(pop, fit, 2)
    assert bool(jnp.all(p2 == pop)) and bool(jnp.all(f2 == fit))


def test_incumbent_sharing():
    cfg = IslandConfig(n_islands=4, pop=16, dim=4, sync_every=5,
                       max_evals=4000, share_incumbent=True)
    res = IslandOptimizer(ALGORITHMS["pso"], cfg).minimize(SPHERE, KEY)
    assert np.isfinite(res.value)


def test_history_monotone():
    cfg = IslandConfig(n_islands=2, pop=16, dim=4, max_evals=6000)
    res = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
    hist = res.history
    assert all(hist[i + 1] <= hist[i] + 1e-9 for i in range(len(hist) - 1))


# --- eval accounting parity (all eight registered policies) ------------------

# Non-default pop (!= the paper's P=50 FA default, not divisible by chunked
# DE's n_chunks) so shape-dependent accounting bugs cannot hide.
PARITY_CASES = [(name, {}) for name in sorted(ALGORITHMS)] + [
    ("de", {"barrier_mode": "chunked", "n_chunks": 8}),
]


@pytest.mark.parametrize("name,params", PARITY_CASES,
                         ids=[n + ("-chunked" if p else "") for n, p in PARITY_CASES])
def test_evals_per_gen_parity(name, params):
    """Charged accounting == actual evaluator rows, per init and per
    generation, for every registered policy: fa's O(P^2) pairwise attraction
    must stay eval-free (exactly pop rows per gen at any pop), and chunked
    DE must charge its clamped-slice overlap (csz * n_chunks rows, not pop).
    """
    from repro.functions import get
    pop, dim = 37, 5
    f = get("sphere", dim)
    counted: list[int] = []

    def counting_evaluator(p):
        n = p.shape[0]                       # static: rows per evaluator call
        jax.debug.callback(lambda: counted.append(n))
        return jnp.sum(p * p, axis=-1)

    algo = ALGORITHMS[name](f=f, evaluator=counting_evaluator,
                            pop=pop, dim=dim, **params)
    barrier = getattr(jax, "effects_barrier", lambda: None)

    state = jax.block_until_ready(algo.init(jax.random.PRNGKey(0)))
    barrier()
    assert sum(counted) == algo.init_evals, (name, counted)

    counted.clear()
    jax.block_until_ready(algo.gen(state, jax.random.PRNGKey(1)))
    barrier()
    assert sum(counted) == algo.evals_per_gen, (name, counted)


# --- chunked DE draws only its chunk's rows, on the same random stream --------

def _chunked_gen_oracle(f, evaluator, pop, strategy, w=0.5, px=0.2, n_chunks=8):
    """Chunked DE as it was first written: every chunk builds the whole
    population's trials with ``_trials`` and keeps its own rows."""
    from repro.core import de
    from repro.core.islands import clip_box, track_best
    csz = max(1, pop // n_chunks)

    def gen(state, key):
        def body(c, carry):
            p, fit = carry
            start = jnp.minimum(c * csz, pop - csz)
            trial = jax.lax.dynamic_slice_in_dim(clip_box(de._trials(
                p, p[jnp.argmin(fit)], jax.random.fold_in(key, c), w, px,
                strategy), f.lo, f.hi), start, csz, 0)
            cur_f = jax.lax.dynamic_slice_in_dim(fit, start, csz, 0)
            cur_p = jax.lax.dynamic_slice_in_dim(p, start, csz, 0)
            tfit = evaluator(trial)
            better = tfit <= cur_f
            p = jax.lax.dynamic_update_slice_in_dim(
                p, jnp.where(better[:, None], trial, cur_p), start, 0)
            fit = jax.lax.dynamic_update_slice_in_dim(
                fit, jnp.where(better, tfit, cur_f), start, 0)
            return p, fit

        p, fit = jax.lax.fori_loop(0, -(-pop // csz), body,
                                   (state["pop"], state["fit"]))
        return track_best(state, p, fit)

    return gen


@pytest.mark.parametrize("n_islands", [1, 3])
@pytest.mark.parametrize("strategy", ["rand1bin", "best1bin"])
@pytest.mark.parametrize("pop", [32, 37])
def test_de_chunked_is_bit_identical_to_full_population_trials(pop, strategy,
                                                                n_islands):
    """Each chunk builds only its own rows' trials, from the rows of the
    full-population draw it used to make and slice: a fixed seed gives the
    same generations bit for bit (pop 37 has a clamped, overlapping last
    chunk; three islands run under ``vmap`` with batched keys)."""
    from repro.core import de
    dim = 11
    f = get("rastrigin")
    evaluator = jax.vmap(f.fn)
    algo = de.make(f, evaluator, pop, dim, strategy=strategy,
                   barrier_mode="chunked")
    oracle = _chunked_gen_oracle(f, evaluator, pop, strategy)
    keys = jax.random.split(jax.random.PRNGKey(11), n_islands)
    new = old = jax.vmap(algo.init)(keys)
    step_new, step_old = jax.jit(jax.vmap(algo.gen)), jax.jit(jax.vmap(oracle))
    for g in range(4):
        gkeys = jax.vmap(lambda k: jax.random.fold_in(k, g))(keys)
        new, old = step_new(new, gkeys), step_old(old, gkeys)
        for name in ("pop", "fit", "best_arg", "best_val"):
            np.testing.assert_array_equal(np.asarray(new[name]),
                                          np.asarray(old[name]), err_msg=name)
    assert not np.array_equal(np.asarray(new["pop"]),
                              np.asarray(jax.vmap(algo.init)(keys)["pop"]))


def _primitives(jaxpr):
    """The name of every primitive in ``jaxpr`` and the jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subs(x)

    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in subs(tuple(eqn.params.values())):
            yield from _primitives(sub)


@pytest.mark.parametrize("strategy", ["rand1bin", "best1bin"])
@pytest.mark.parametrize("pop", [32, 37])
def test_de_chunked_draws_every_chunk_before_the_loop(pop, strategy):
    """No chunk key reads the population, so every key, donor and forced
    coordinate is drawn before the chunk loop: its body hashes only the
    chunk's crossover uniforms (one ``threefry2x32``) and derives, wraps or
    draws no key (pop 37 has a clamped, overlapping last chunk)."""
    from repro.core import de
    f = get("rastrigin")
    algo = de.make(f, jax.vmap(f.fn), pop, 11, strategy=strategy,
                   barrier_mode="chunked")
    state = jax.eval_shape(algo.init, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(algo.gen)(state, jax.random.PRNGKey(1)).jaxpr
    loops = [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]
    assert len(loops) == 1, loops
    inside = list(_primitives(loops[0].params["jaxpr"].jaxpr))
    assert inside.count("threefry2x32") == 1, inside
    keys = {"random_fold_in", "random_split", "random_bits", "random_wrap"}
    assert not keys & set(inside), inside
    assert keys <= set(_primitives(jaxpr))


@pytest.mark.parametrize("n_islands", [1, 3])
@pytest.mark.parametrize("strategy", ["rand1bin", "best1bin"])
@pytest.mark.parametrize("pop", [32, 37])
def test_de_chunked_keeps_its_draws_rows_without_a_gather(pop, strategy,
                                                          n_islands):
    """Each chunk's rows of the integer draws are cut by static slices before
    the chunk loop, never gathered (the TPU gathers one element at a time):
    nothing ahead of the loop is a ``gather``, with or without a clamped,
    overlapping last chunk (pop 37), for one island and three under
    ``vmap``. After the loop ``track_best`` picks each island's best row,
    which under ``vmap`` is a gather of one row per island."""
    from jax.extend.core import Jaxpr

    from repro.core import de
    f = get("rastrigin")
    algo = de.make(f, jax.vmap(f.fn), pop, 11, strategy=strategy,
                   barrier_mode="chunked")
    keys = jax.random.split(jax.random.PRNGKey(1), n_islands)
    state = jax.eval_shape(jax.vmap(algo.init), keys)
    jaxpr = jax.make_jaxpr(jax.vmap(algo.gen))(state, keys).jaxpr
    (loop,) = [i for i, e in enumerate(jaxpr.eqns)
               if e.primitive.name in ("scan", "while")]
    ahead = list(_primitives(Jaxpr(jaxpr.constvars, jaxpr.invars, [],
                                   jaxpr.eqns[:loop])))
    assert {"random_bits", "slice"} <= set(ahead), ahead
    assert "gather" not in ahead, ahead


@pytest.mark.parametrize("n_islands", [1, 3])
@pytest.mark.parametrize("pop", [32, 37])
def test_de_chunked_round_draws_ahead_bit_identically(pop, n_islands):
    """Through ``MetaHeuristic.draw`` the engine draws a round's chunk donors
    and forced coordinates before the round's scan, whose body then draws no
    random bits; a run is bit-identical to one whose every generation draws
    its own (``draw`` unset), with a ring between three islands and a
    clamped, overlapping last chunk at pop 37."""
    import dataclasses

    from repro.core import de
    f = get("rastrigin", 11)
    cfg = IslandConfig(n_islands=n_islands, pop=pop, dim=11, sync_every=3,
                       migration="ring" if n_islands > 1 else "none",
                       max_evals=pop * n_islands * 13)

    def maker(ahead: bool):
        def make(**kw):
            algo = de.make(barrier_mode="chunked", **kw)
            assert algo.draw is not None
            return algo if ahead else dataclasses.replace(algo, draw=None)
        return make

    runs = []
    for ahead in (True, False):
        opt = IslandOptimizer(maker(ahead), cfg)
        algo = opt._build(f)
        state = jax.eval_shape(lambda k: opt._init_state(algo, k), KEY)
        jaxpr = jax.make_jaxpr(opt._round_fn(algo))(state, KEY).jaxpr
        (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
        inside = set(_primitives(scan.params["jaxpr"].jaxpr))
        assert ("random_bits" in inside) is not ahead, inside
        assert "random_bits" in set(_primitives(jaxpr))
        runs.append(opt.minimize(f, KEY))
    a, b = runs
    assert a.value == b.value
    np.testing.assert_array_equal(np.asarray(a.arg), np.asarray(b.arg))
    np.testing.assert_array_equal(np.asarray(a.history), np.asarray(b.history))


@pytest.mark.parametrize("kind", ["raw", "typed", "rbg"])
@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "fallback"])
def test_uniform_rows_match_the_full_draw(partitionable, kind, monkeypatch):
    """The row-block draw equals ``uniform(k, (P, D))[s:s+n]`` bit for bit at
    every start, clamped last block included, for raw and typed threefry
    keys; with partitionable threefry off, or a key of another PRNG, it
    takes the full draw and slices it, and still equals it."""
    from repro.core import de
    P, D, n = 37, 13, 4
    key = {"raw": jax.random.PRNGKey(5), "typed": jax.random.key(5),
           "rbg": jax.random.key(5, impl="rbg")}[kind]
    uniform, draws = jax.random.uniform, []

    def counted_uniform(k, shape, *a, **kw):
        draws.append(shape)
        return uniform(k, shape, *a, **kw)

    with jax.threefry_partitionable(partitionable):
        full = np.asarray(uniform(key, (P, D)))
        monkeypatch.setattr(jax.random, "uniform", counted_uniform)
        rows = jax.jit(de._uniform_rows, static_argnums=(1, 2, 4))
        for s in (0, 4, 9, P - n):
            np.testing.assert_array_equal(np.asarray(rows(key, P, D, s, n)),
                                          full[s:s + n])
    fast = partitionable and kind != "rbg"
    assert draws == ([] if fast else [(P, D)]), draws
