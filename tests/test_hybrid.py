"""Hybrid memetic layer tests (DESIGN.md §6–§7): batched polish semantics and
eval accounting, in-scan hybrid determinism/parity across minimize /
minimize_many / host-stepped paths, shape-class separation, the two-stage
pipeline, and the JSONL service path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ALGORITHMS, IslandConfig, IslandOptimizer, OptRequest,
                        ShapeBucketScheduler, explore_then_polish,
                        explore_then_polish_many)
from repro.functions import get
from repro.launch.opt_serve import OptimizationService
from repro.optim.descent import (PolishConfig, make_polish,
                                 polish_evals_per_point)

KEY = jax.random.PRNGKey(7)
METHODS = ("asd", "fcg", "avd", "bfgs")

HYBRID = dict(polish="asd", polish_every=2, polish_topk=3, polish_steps=2)


def _island_cfg(**kw):
    base = dict(n_islands=2, pop=16, dim=6, sync_every=5, migration="ring",
                max_evals=5000)
    base.update(kw)
    return IslandConfig(**base)


def _starts(f, k, dim, key=KEY):
    xs = jax.random.uniform(key, (k, dim), minval=f.lo, maxval=f.hi)
    return xs, jax.vmap(f.fn)(xs)


# --- polish primitive --------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_polish_monotone_and_jit_safe(method):
    f = get("rosenbrock")
    xs, fs = _starts(f, 5, 6)
    cfg = PolishConfig(method=method, steps=4)
    polish = make_polish(f, None, 6, cfg)
    xs2, fs2 = polish(xs, fs)                      # eager
    assert bool(jnp.all(fs2 <= fs))                # monotone by construction
    assert bool(jnp.any(fs2 < fs))                 # and actually descends
    jxs2, jfs2 = jax.jit(polish)(xs, fs)           # jitted: same trajectory
    np.testing.assert_array_equal(np.asarray(fs2), np.asarray(jfs2))
    np.testing.assert_array_equal(np.asarray(xs2), np.asarray(jxs2))


@pytest.mark.parametrize("method", METHODS)
def test_polish_eval_accounting_is_exact(method):
    """The evaluator sees exactly polish_evals_per_point(dim)·K rows per
    step — counted at trace time (scan traces its body once, so the counter
    observes one step's cost)."""
    f = get("sphere")
    dim, k = 5, 3
    xs, fs = _starts(f, k, dim)
    cfg = PolishConfig(method=method, steps=4)
    rows = [0]

    def counting_eval(pop):
        rows[0] += pop.shape[0]
        return jax.vmap(f.fn)(pop)

    make_polish(f, counting_eval, dim, cfg)(xs, fs)
    per_step = polish_evals_per_point(dim, cfg) // cfg.steps
    assert rows[0] == k * per_step


@pytest.mark.parametrize("method", METHODS)
def test_polish_batched_matches_single_start(method):
    """Polishing K starts in one batch follows the same trajectory as
    polishing each start alone. Rows are arithmetically independent, but the
    batch shape changes XLA's reduction fusion, so f32 noise (~1e-7) can
    compound across steps — parity is trajectory-level, not bit-level."""
    f = get("levy")
    cfg = PolishConfig(method=method, steps=3)
    polish = make_polish(f, None, 6, cfg)
    xs, fs = _starts(f, 4, 6)
    bx, bf = polish(xs, fs)
    for i in range(4):
        sx, sf = polish(xs[i:i + 1], fs[i:i + 1])
        np.testing.assert_allclose(np.asarray(sx[0]), np.asarray(bx[i]),
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(float(sf[0]), float(bf[i]),
                                   rtol=1e-3, atol=1e-5)


def test_polish_config_rejects_unknown_method():
    with pytest.raises(ValueError, match="unknown polish method"):
        PolishConfig(method="adam")


# --- in-scan hybrid engine ---------------------------------------------------

def test_hybrid_fixed_seed_determinism():
    f = get("rosenbrock")
    r1 = IslandOptimizer(ALGORITHMS["de"], _island_cfg(**HYBRID)).minimize(f, KEY)
    r2 = IslandOptimizer(ALGORITHMS["de"], _island_cfg(**HYBRID)).minimize(f, KEY)
    assert r1.value == r2.value and r1.n_evals == r2.n_evals
    np.testing.assert_array_equal(np.asarray(r1.history), np.asarray(r2.history))
    np.testing.assert_array_equal(np.asarray(r1.arg), np.asarray(r2.arg))


def test_hybrid_budget_counts_polish_evals():
    """Polish work is charged to max_evals: the hybrid stays within budget
    and runs measurably fewer generations than the plain config."""
    f = get("rosenbrock")
    plain = IslandOptimizer(ALGORITHMS["de"], _island_cfg()).minimize(f, KEY)
    hyb = IslandOptimizer(ALGORITHMS["de"], _island_cfg(**HYBRID)).minimize(f, KEY)
    assert hyb.n_evals <= 5000
    assert hyb.n_gens < plain.n_gens
    # exact accounting: init + rounds*per_round + polish events*per_event
    cfg = _island_cfg(**HYBRID)
    pcfg = PolishConfig(method="asd", steps=cfg.polish_steps)
    per_event = (polish_evals_per_point(cfg.dim, pcfg)
                 * cfg.polish_topk * cfg.n_islands)
    n_rounds = hyb.n_gens // cfg.sync_every
    per_round = cfg.pop * cfg.n_islands * cfg.sync_every
    expect = (cfg.pop * cfg.n_islands + n_rounds * per_round
              + (n_rounds // cfg.polish_every) * per_event)
    assert hyb.n_evals == expect


def test_hybrid_minimize_many_bit_identical():
    """Jobs-axis hybrid trajectories == standalone hybrid minimize."""
    f = get("rastrigin")
    cfg = _island_cfg(**HYBRID)
    seq = [IslandOptimizer(ALGORITHMS["de"], cfg).minimize(f, jax.random.PRNGKey(s))
           for s in (0, 4)]
    many = IslandOptimizer(ALGORITHMS["de"], cfg).minimize_many(
        f, jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(4)]))
    for m, s in zip(many, seq):
        assert m.value == s.value and m.n_evals == s.n_evals
        assert bool(jnp.all(m.arg == s.arg))
        np.testing.assert_array_equal(np.asarray(m.history),
                                      np.asarray(s.history))


def test_hybrid_host_stepped_matches_device_resident():
    """The host-stepped BucketStepper (one job) polishes on the resident
    scan's cadence: same history, value and evaluation count, bit for bit."""
    f = get("sphere")
    opt = IslandOptimizer(ALGORITHMS["de"], _island_cfg(**HYBRID))
    dev = opt.minimize(f, KEY)
    stepper = opt.bucket_stepper(f)
    state, round_keys = stepper.init(KEY[None])
    history = []
    for r in range(stepper.n_rounds):
        state, point = stepper.step(state, round_keys, r)
        history.append(np.asarray(point)[0])
    _, vals = stepper.best(state)
    assert float(vals[0]) == dev.value
    assert stepper.evals_done(stepper.n_rounds) == dev.n_evals
    np.testing.assert_array_equal(np.asarray(dev.history), np.asarray(history))


@pytest.mark.parametrize("method", ("fcg", "avd"))
def test_hybrid_other_polish_methods_run(method):
    f = get("griewank")
    cfg = _island_cfg(polish=method, polish_every=2, polish_topk=2,
                      polish_steps=2)
    res = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(f, KEY)
    assert np.isfinite(res.value) and res.n_evals <= 5000


def test_hybrid_through_pallas_backend():
    """Polish gradients/ladders ride the same pluggable evaluator as
    generation steps: the whole hybrid run works on the pallas backend
    (interpret mode off-TPU), budget accounting unchanged."""
    from repro.core import ExecutorConfig
    f = get("rastrigin")
    cfg = _island_cfg(max_evals=3000, **HYBRID)
    res = IslandOptimizer(ALGORITHMS["de"], cfg,
                          exec_cfg=ExecutorConfig(backend="pallas")).minimize(
        f, KEY)
    xla = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(f, KEY)
    assert np.isfinite(res.value) and res.n_evals == xla.n_evals <= 3000


# --- shape-class / scheduler / service --------------------------------------

def test_polish_params_join_shape_class():
    base = dict(fn="sphere", dim=6, pop=16, max_evals=4000)
    plain = OptRequest(**base)
    hybrid = OptRequest(**base, polish="asd")
    assert plain.shape_class() != hybrid.shape_class()
    assert (OptRequest(**base, polish="asd", polish_topk=2).shape_class()
            != hybrid.shape_class())
    assert (OptRequest(**base, polish="asd", seed=9).shape_class()
            == hybrid.shape_class())


def test_scheduler_hybrid_bucket_parity():
    base = dict(fn="rosenbrock", dim=6, pop=16, n_islands=2, sync_every=5,
                max_evals=4000)
    sched = ShapeBucketScheduler()
    jid_p = sched.submit(OptRequest(**base))
    jid_h = sched.submit(OptRequest(**base, **HYBRID))
    assert len(sched.pending_buckets()) == 2     # hybrid != plain bucket
    sched.flush()
    assert sched.n_dispatches == 2
    got = sched.result(jid_h).result
    direct = IslandOptimizer(
        ALGORITHMS["de"],
        _island_cfg(max_evals=4000, **HYBRID)).minimize(
            get("rosenbrock"), jax.random.PRNGKey(0))
    assert got.value == direct.value and got.n_evals == direct.n_evals
    assert sched.result(jid_p).status == "done"


def test_service_hybrid_jsonl_roundtrip():
    svc = OptimizationService()
    r = svc.handle({"op": "submit", "request": {
        "fn": "sphere", "dim": 4, "pop": 16, "max_evals": 3000, "seed": 1,
        "polish": "asd", "polish_every": 2, "polish_topk": 2,
        "polish_steps": 2}})
    out = svc.handle({"op": "result", "id": r["id"]})
    assert out["status"] == "done" and out["n_evals"] <= 3000


# --- two-stage pipeline ------------------------------------------------------

def test_explore_then_polish_improves_and_accounts():
    f = get("rosenbrock")
    opt = IslandOptimizer(ALGORITHMS["de"], _island_cfg())
    base = opt.minimize(f, KEY)
    pcfg = PolishConfig(steps=8)
    res = explore_then_polish(opt, f, KEY, pcfg)
    assert res.value <= base.value
    assert res.n_evals == base.n_evals + polish_evals_per_point(6, pcfg)


def test_explore_then_polish_many_matches_single():
    f = get("rosenbrock")
    opt = IslandOptimizer(ALGORITHMS["de"], _island_cfg())
    pcfg = PolishConfig(steps=6)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (0, 2, 5)])
    many = explore_then_polish_many(opt, f, keys, pcfg)
    for k, m in zip((0, 2, 5), many):
        single = explore_then_polish(opt, f, jax.random.PRNGKey(k), pcfg)
        np.testing.assert_allclose(m.value, single.value, rtol=1e-6)
        assert m.n_evals == single.n_evals
