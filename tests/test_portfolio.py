"""Heterogeneous algorithm-portfolio island tests (DESIGN.md §10).

Three tiers:

* Determinism contract: a fixed-seed HOMOGENEOUS portfolio (every island
  ``algo_id=de``) is bit-identical to the plain ``algo_maker``-driven engine
  across ``minimize``, ``minimize_many`` and sharded runs (the 8-device case
  runs under CI's distributed-smoke job). Mixed portfolios are bit-
  reproducible for a fixed device layout; across layouts they are value-
  stable only (XLA may fuse the ``lax.switch`` branches differently per
  batch size and reassociate the evaluator's reductions).
* Cross-algorithm migration semantics: migrants carry pos/fit only; the
  destination policy re-initializes its aux slots on adoption (PSO velocity
  zeroed, pbest restarted at the migrant; GA age reset, ``alive`` revived).
* Stack plumbing: shape-class separation, scheduler bucket parity, JSONL
  service round trip, and the registry's schema invariants.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ALGORITHMS, IslandConfig, IslandOptimizer, MeshConfig,
                        OptRequest, ShapeBucketScheduler)
from repro.core import portfolio as pf
from repro.functions import get

KEY = jax.random.PRNGKey(11)
N_DEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _cfg(**kw):
    base = dict(n_islands=4, pop=16, dim=6, sync_every=5, migration="ring",
                max_evals=6000)
    base.update(kw)
    return IslandConfig(**base)


def _assert_same(a, b):
    assert a.value == b.value
    assert a.n_evals == b.n_evals and a.n_gens == b.n_gens
    assert np.array_equal(np.asarray(a.arg), np.asarray(b.arg))
    assert np.array_equal(np.asarray(a.history), np.asarray(b.history))


# --- registry / schema -------------------------------------------------------

def test_registry_covers_all_engine_algorithms():
    """Every ALGORITHMS entry is registered with a unique, stable algo_id."""
    assert set(pf.REGISTRY) == set(ALGORITHMS)
    ids = [s.algo_id for s in pf.REGISTRY.values()]
    assert len(ids) == len(set(ids))
    # frozen wire ids — renumbering breaks serialized requests
    assert pf.REGISTRY["de"].algo_id == 0
    assert pf.REGISTRY["ga"].algo_id == 1
    assert pf.REGISTRY["pso"].algo_id == 2


def test_schema_is_registry_wide_maximum():
    nv, np_, ns = pf.schema()
    assert nv >= 2 and np_ >= 2 and ns >= 1   # pso: 2 vec; ga: 2 ind; sa: 1 scl


def test_unified_state_shares_one_pytree_structure():
    """Every policy's unified init produces the same pytree structure — the
    precondition for lax.switch branches."""
    f = get("sphere", 4)
    ev = f.eval_population
    structs = set()
    for name, spec in pf.REGISTRY.items():
        algo = spec.maker(f=f, evaluator=ev, pop=8, dim=4)
        u = pf.UnifiedPolicy(spec, algo, 8, 4).init(KEY)
        structs.add(jax.tree.structure(u))
        assert u["alive"].dtype == jnp.bool_ and u["alive"].shape == (8,)
    assert len(structs) == 1


def test_expand_cycles_and_validates():
    assert pf.expand(("de", "pso"), 5) == ("de", "pso", "de", "pso", "de")
    assert pf.expand(("de", "pso", "sa"), 3) == ("de", "pso", "sa")
    with pytest.raises(ValueError, match="unknown"):
        pf.expand(("nope",), 2)
    with pytest.raises(ValueError, match="empty"):
        pf.expand((), 2)
    # over-length specs are rejected, never silently truncated
    with pytest.raises(ValueError, match="only 2 islands"):
        pf.expand(("de", "pso", "sa"), 2)


def test_build_portfolio_rejects_params_for_absent_policies():
    f = get("sphere", 4)
    with pytest.raises(ValueError, match="not in the portfolio"):
        pf.build_portfolio(("de", "pso"), f, f.eval_population, 8, 4,
                           params={"sa": {"T0": 1.0}})


# --- determinism contract ----------------------------------------------------

@pytest.mark.parametrize("algo", ["de", "pso", "sa", "bh"])
def test_homogeneous_portfolio_bit_identical_minimize(algo):
    """The contract holds for every policy, not just de: the plain engine
    applies the same registered adopt rules (adopt_native), so a homogeneous
    portfolio and the algo_maker engine share one trajectory."""
    f = get("rastrigin", 6)
    plain = IslandOptimizer(ALGORITHMS[algo], _cfg()).minimize(f, KEY)
    port = IslandOptimizer(None, _cfg(portfolio=(algo,))).minimize(f, KEY)
    _assert_same(plain, port)


def test_homogeneous_de_portfolio_bit_identical_minimize_many():
    f = get("sphere", 6)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (0, 3, 11)])
    plain = IslandOptimizer(ALGORITHMS["de"], _cfg()).minimize_many(f, keys)
    port = IslandOptimizer(None, _cfg(portfolio=("de",))).minimize_many(f, keys)
    for a, b in zip(plain, port):
        _assert_same(a, b)


def test_homogeneous_de_portfolio_bit_identical_one_device_mesh():
    f = get("rastrigin", 6)
    plain = IslandOptimizer(ALGORITHMS["de"], _cfg()).minimize(f, KEY)
    port = IslandOptimizer(None, _cfg(portfolio=("de",)),
                           mesh_cfg=MeshConfig(devices=1)).minimize(f, KEY)
    _assert_same(plain, port)


@needs8
def test_homogeneous_de_portfolio_bit_identical_eight_devices():
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=8, max_evals=8000)
    plain = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(f, KEY)
    port = IslandOptimizer(None, dataclasses.replace(cfg, portfolio=("de",)),
                           mesh_cfg=MeshConfig(devices=8)).minimize(f, KEY)
    _assert_same(plain, port)


@needs8
def test_homogeneous_de_portfolio_bit_identical_eight_devices_many():
    f = get("levy", 6)
    cfg = _cfg(n_islands=8, max_evals=6000)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (0, 4)])
    plain = IslandOptimizer(ALGORITHMS["de"], cfg).minimize_many(f, keys)
    port = IslandOptimizer(None, dataclasses.replace(cfg, portfolio=("de",)),
                           mesh_cfg=MeshConfig(devices=8)).minimize_many(f, keys)
    for a, b in zip(plain, port):
        _assert_same(a, b)


def test_mixed_portfolio_deterministic_and_improves():
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=6, max_evals=9000, portfolio=("de", "pso", "sa"))
    params = {"sa": {"T0": 50.0}}
    r1 = IslandOptimizer(None, cfg, params=params).minimize(f, KEY)
    r2 = IslandOptimizer(None, cfg, params=params).minimize(f, KEY)
    _assert_same(r1, r2)
    assert r1.value < 50.0 and np.isfinite(r1.value)
    assert r1.n_evals <= cfg.max_evals
    hist = np.asarray(r1.history)
    assert np.all(np.diff(hist) <= 0)          # incumbent is monotone


def test_mixed_portfolio_minimize_many_matches_minimize():
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=6, max_evals=9000, portfolio=("de", "pso", "sa"))
    seeds = (0, 5)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    many = IslandOptimizer(None, cfg).minimize_many(f, keys)
    for s, got in zip(seeds, many):
        solo = IslandOptimizer(None, cfg).minimize(f, jax.random.PRNGKey(s))
        _assert_same(solo, got)


def test_mixed_portfolio_one_device_mesh_bit_identical():
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=6, max_evals=9000, portfolio=("de", "pso", "sa"))
    u = IslandOptimizer(None, cfg).minimize(f, KEY)
    s = IslandOptimizer(None, cfg, mesh_cfg=MeshConfig(devices=1)).minimize(f, KEY)
    _assert_same(u, s)


@needs8
def test_mixed_portfolio_eight_devices_value_stable():
    """Across device layouts mixed portfolios are value-stable, not bit-
    identical: XLA fuses the switch branches per batch size and may
    reassociate the evaluator's reductions (DESIGN.md §10)."""
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=8, max_evals=12000,
               portfolio=("de", "pso", "sa", "ea"))
    u = IslandOptimizer(None, cfg).minimize(f, KEY)
    s = IslandOptimizer(None, cfg, mesh_cfg=MeshConfig(devices=8)).minimize(f, KEY)
    s2 = IslandOptimizer(None, cfg, mesh_cfg=MeshConfig(devices=8)).minimize(f, KEY)
    _assert_same(s, s2)                        # fixed layout: bit-reproducible
    np.testing.assert_allclose(np.asarray(u.history), np.asarray(s.history),
                               rtol=1e-5)
    assert u.n_evals == s.n_evals and u.n_gens == s.n_gens


def test_portfolio_composes_with_polish_and_incumbent_sharing():
    f = get("rosenbrock", 6)
    cfg = _cfg(n_islands=4, max_evals=8000, portfolio=("de", "pso"),
               share_incumbent=True, polish="asd", polish_every=2,
               polish_topk=2, polish_steps=2)
    r1 = IslandOptimizer(None, cfg).minimize(f, KEY)
    r2 = IslandOptimizer(None, cfg).minimize(f, KEY)
    _assert_same(r1, r2)
    assert r1.n_evals <= cfg.max_evals


def test_portfolio_heterogeneous_budget_accounting():
    """Islands charge their OWN policy's evals_per_gen: a ga island (n_off
    per gen) costs less than a de island (pop per gen), and the round total
    is the per-island sum."""
    f = get("sphere", 4)
    cfg = _cfg(n_islands=2, pop=16, dim=4, migration="none",
               portfolio=("de", "ga"), max_evals=2000)
    opt = IslandOptimizer(None, cfg)
    port = opt._build(f)
    n_off = max(1, 16 // 4)
    assert port.per_gen_total == 16 + n_off
    assert port.init_total == 32
    res = opt.minimize(f, KEY)
    assert res.n_evals <= cfg.max_evals
    rounds = res.n_gens // cfg.sync_every
    assert res.n_evals == 32 + rounds * cfg.sync_every * (16 + n_off)


def test_portfolio_mode_validation():
    with pytest.raises(ValueError, match="algo_maker=None"):
        IslandOptimizer(ALGORITHMS["de"], _cfg(portfolio=("de", "pso")))
    with pytest.raises(ValueError, match="n_islands > 1"):
        IslandOptimizer(None, _cfg(n_islands=1, migration="none",
                                   portfolio=("de",)))
    with pytest.raises(ValueError, match="algo_maker is required"):
        IslandOptimizer(None, _cfg())


# --- cross-algorithm migration semantics ------------------------------------

def _unified(name, f, pop=6, dim=3, **kw):
    spec = pf.REGISTRY[name]
    algo = spec.maker(f=f, evaluator=f.eval_population, pop=pop, dim=dim, **kw)
    return pf.UnifiedPolicy(spec, algo, pop, dim)


def test_adopt_reinitializes_pso_aux_slots():
    f = get("sphere", 3)
    up = _unified("pso", f)
    u = up.init(KEY)
    # pretend slots 1 and 4 adopted migrants: pop/fit already overwritten
    mask = jnp.asarray([False, True, False, False, True, False])
    mig_pos = jnp.full((3,), 7.0)
    u = {**u, "pop": u["pop"].at[1].set(mig_pos).at[4].set(-mig_pos),
         "fit": u["fit"].at[1].set(0.5).at[4].set(0.25)}
    v = up.adopt(u, mask)
    vel, pbest = v["aux_vec"][0], v["aux_vec"][1]
    pbest_f = v["aux_ind"][0]
    assert np.all(np.asarray(vel[1]) == 0) and np.all(np.asarray(vel[4]) == 0)
    assert np.array_equal(np.asarray(pbest[1]), np.asarray(v["pop"][1]))
    assert np.array_equal(np.asarray(pbest[4]), np.asarray(v["pop"][4]))
    assert pbest_f[1] == 0.5 and pbest_f[4] == 0.25
    # untouched rows keep their aux state
    assert np.array_equal(np.asarray(vel[0]), np.asarray(u["aux_vec"][0][0]))
    assert np.array_equal(np.asarray(pbest[2]), np.asarray(u["aux_vec"][1][2]))
    assert np.all(np.asarray(v["alive"]))


def test_adopt_revives_and_rejuvenates_ga_slots():
    f = get("sphere", 3)
    up = _unified("ga", f, age_mean=10.0, age_sd=0.0)
    u = up.init(KEY)
    # age everyone, kill slot 2, then adopt a migrant into it
    u = {**u, "aux_ind": u["aux_ind"].at[0].set(9.0),
         "alive": u["alive"].at[2].set(False)}
    mask = jnp.asarray([False, False, True, False, False, False])
    v = up.adopt(u, mask)
    age, limit = v["aux_ind"][0], v["aux_ind"][1]
    assert age[2] == 0.0                        # migrant arrives newborn
    assert age[0] == 9.0                        # non-adopted ages untouched
    assert limit[2] == u["aux_ind"][1][2]       # slot keeps its drawn limit
    assert bool(v["alive"][2])                  # revived
    assert not bool(u["alive"][2])


def test_adopt_keeps_per_island_scalars():
    f = get("sphere", 3)
    for name in ("sa", "ea", "fa"):
        up = _unified(name, f)
        u = up.init(KEY)
        u = {**u, "aux_scl": u["aux_scl"].at[0].set(3.25)}
        v = up.adopt(u, jnp.ones((6,), bool))
        assert v["aux_scl"][0] == 3.25


def test_ring_migration_across_policies_adopts_only_better():
    """2-island (de -> pso) ring: the pso island adopts de's best only when
    it beats its own worst, and the adopted slot's velocity re-initializes
    inside the jitted engine run."""
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=2, pop=12, max_evals=4000, sync_every=3,
               n_migrants=2, portfolio=("de", "pso"))
    r1 = IslandOptimizer(None, cfg).minimize(f, KEY)
    r2 = IslandOptimizer(None, cfg).minimize(f, KEY)
    _assert_same(r1, r2)
    assert np.isfinite(r1.value)
    hist = np.asarray(r1.history)
    assert np.all(np.diff(hist) <= 0)


def test_starvation_migration_into_aging_ga_island():
    """ga islands age out; starvation re-seeds them from the other policies'
    best, and the adopted slots come back alive (the engine-level aux
    re-init path)."""
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=4, pop=12, max_evals=8000, migration="starvation",
               portfolio=("ga", "pso", "ga", "sa"))
    params = {"ga": {"age_mean": 6.0, "age_sd": 1.0}, "sa": {"T0": 20.0}}
    r1 = IslandOptimizer(None, cfg, params=params).minimize(f, KEY)
    r2 = IslandOptimizer(None, cfg, params=params).minimize(f, KEY)
    _assert_same(r1, r2)
    assert np.isfinite(r1.value) and r1.value < 100.0


def test_plain_ga_starvation_revives_adopted_slots():
    """The engine-level fix the portfolio layer generalizes: in plain mode a
    ga island's adopted migrants revive AND their age resets — else the next
    generation's age > age_limit check re-kills the migrant the slot just
    adopted. Enforced by bit-identity with the homogeneous ga portfolio,
    whose adopt rule (age zero, limit keep, alive revive) is the same."""
    f = get("rastrigin", 6)
    for mig in ("starvation", "ring"):
        cfg = _cfg(n_islands=4, pop=12, max_evals=8000, migration=mig)
        params = {"age_mean": 6.0, "age_sd": 1.0}
        plain = IslandOptimizer(ALGORITHMS["ga"], cfg,
                                params=params).minimize(f, KEY)
        port = IslandOptimizer(
            None, dataclasses.replace(cfg, portfolio=("ga",)),
            params={"ga": params}).minimize(f, KEY)
        _assert_same(plain, port)
        assert np.isfinite(plain.value)


def test_homogeneous_portfolio_starvation_matches_plain_under_eviction():
    """Starvation counts live slots as isfinite(fit) for policies that do not
    own an alive mask; the portfolio's all-True common mask must not change
    that. An objective that fails on half the domain (executor evicts to
    +inf) makes the starvation trigger depend on it — plain and homogeneous
    portfolio must still agree bit-for-bit."""
    from repro.functions.benchmarks import Function

    def half_bad(x):
        s = jnp.sum(x * x, axis=-1)
        return jnp.where(x[..., 0] > 0.0, jnp.nan, s)

    f = Function("half_bad_sphere", half_bad, -10.0, 10.0)
    cfg = _cfg(n_islands=4, pop=12, max_evals=5000, migration="starvation")
    plain = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(f, KEY)
    port = IslandOptimizer(None, dataclasses.replace(cfg, portfolio=("de",))
                           ).minimize(f, KEY)
    _assert_same(plain, port)
    assert np.isfinite(plain.value)


# --- stack plumbing ----------------------------------------------------------

def test_portfolio_joins_shape_class():
    base = dict(fn="sphere", n_islands=4)
    a = OptRequest(**base)
    b = OptRequest(portfolio=("de", "pso"), **base)
    c = OptRequest(portfolio=("de", "sa"), **base)
    assert len({a.shape_class(), b.shape_class(), c.shape_class()}) == 3
    assert (OptRequest(portfolio=("de", "pso"), seed=0, **base).shape_class()
            == OptRequest(portfolio=("de", "pso"), seed=7, **base).shape_class())
    # algo is ignored in portfolio mode and normalized out of the bucket key,
    # so habitually-set algo values cannot split identical portfolio jobs
    assert (OptRequest(portfolio=("de", "pso"), algo="de", **base).shape_class()
            == OptRequest(portfolio=("de", "pso"), algo="ga", **base).shape_class())


def test_from_dict_freezes_portfolio_and_nested_params():
    req = OptRequest.from_dict({
        "fn": "rastrigin", "n_islands": 6, "portfolio": ["de", "pso", "sa"],
        "params": {"sa": {"T0": 50.0}, "de": {"w": 0.7}}})
    assert req.portfolio == ("de", "pso", "sa")
    assert isinstance(req.params, tuple)
    hash(req.shape_class())                    # must stay hashable
    assert dict(req.params)["sa"] == (("T0", 50.0),)


def test_scheduler_portfolio_bucket_matches_standalone():
    base = {"fn": "rastrigin", "dim": 6, "pop": 16, "n_islands": 6,
            "sync_every": 5, "max_evals": 6000,
            "portfolio": ["de", "pso", "sa"], "params": {"sa": {"T0": 50.0}}}
    sched = ShapeBucketScheduler()
    ids = [sched.submit(OptRequest.from_dict({**base, "seed": s}))
           for s in (0, 4)]
    plain_id = sched.submit(OptRequest(fn="rastrigin", dim=6, pop=16,
                                       n_islands=6, sync_every=5,
                                       max_evals=6000, seed=0))
    assert len(sched.pending_buckets()) == 2   # portfolio and plain split
    assert sched.flush() == 3
    cfg = _cfg(n_islands=6, portfolio=("de", "pso", "sa"))
    f = get("rastrigin", 6)
    for jid, seed in zip(ids, (0, 4)):
        got = sched.result(jid)
        assert got.status == "done"
        expect = IslandOptimizer(None, cfg, params={"sa": {"T0": 50.0}}
                                 ).minimize(f, jax.random.PRNGKey(seed))
        assert got.result.value == expect.value
        assert np.array_equal(np.asarray(got.result.arg),
                              np.asarray(expect.arg))
    assert sched.result(plain_id).status == "done"


def test_opt_serve_portfolio_round_trip():
    from repro.launch.opt_serve import OptimizationService
    svc = OptimizationService(max_batch=8, flush_ms=5.0)
    out = svc.handle({"op": "submit", "request": {
        "fn": "sphere", "dim": 4, "pop": 16, "n_islands": 4,
        "portfolio": ["de", "pso"], "max_evals": 3000, "seed": 0}})
    assert out["status"] == "queued"
    res = svc.handle({"op": "result", "id": out["id"]})
    assert res["status"] == "done" and np.isfinite(res["value"])
    assert len(res["arg"]) == 4
