"""EvalBackend layer + device-resident engine tests (ISSUE 1 acceptance).

Covers: XLA-vs-Pallas(interpret) fitness parity for every registered kernel,
device-resident vs host-stepped engine equivalence on a fixed seed, the
single-host-transfer property of the device-resident path, and the fused-DE
``step_override`` regression.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ALGORITHMS, ExecutorConfig, IslandConfig, IslandOptimizer, de
from repro.core.executor import make_batch_evaluator
from repro.functions import get, make_shifted_rosenbrock
from repro.kernels import registry

KEY = jax.random.PRNGKey(11)
SPHERE = get("sphere")


def _fn(name, dim):
    return make_shifted_rosenbrock(dim) if name == "shifted_rosenbrock" else get(name)


# --- backend parity ----------------------------------------------------------

@pytest.mark.parametrize("name", sorted(registry.registered()))
def test_xla_vs_pallas_parity(name):
    dim, P = 24, 65                       # deliberately unaligned shapes
    f = _fn(name, dim)
    pop = jax.random.uniform(jax.random.fold_in(KEY, hash(name) % 997), (P, dim),
                             minval=f.lo, maxval=f.hi)
    fx = make_batch_evaluator(f, ExecutorConfig(backend="xla"))(pop)
    fp = make_batch_evaluator(f, ExecutorConfig(backend="pallas"))(pop)
    rel = float(jnp.max(jnp.abs(fx - fp) / (jnp.abs(fx) + 1.0)))
    assert rel <= 1e-4, (name, rel)


def test_pallas_backend_unregistered_function_raises():
    with pytest.raises(KeyError, match="weierstrass"):
        make_batch_evaluator(get("weierstrass"), ExecutorConfig(backend="pallas"))


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        make_batch_evaluator(SPHERE, ExecutorConfig(backend="cuda"))


def test_pallas_backend_retry_semantics():
    """The resubmit-once/evict policy is backend-independent: the pallas path
    keeps finite fitness finite and shapes intact."""
    f = get("rastrigin")
    ev = make_batch_evaluator(f, ExecutorConfig(backend="pallas", retry_bad=True))
    pop = jax.random.uniform(KEY, (13, 8), minval=f.lo, maxval=f.hi)
    fit = ev(pop)
    assert fit.shape == (13,) and bool(jnp.all(jnp.isfinite(fit)))


def test_pallas_backend_under_island_engine():
    cfg = IslandConfig(n_islands=2, pop=16, dim=8, sync_every=5, max_evals=4000)
    res = IslandOptimizer(ALGORITHMS["de"], cfg,
                          exec_cfg=ExecutorConfig(backend="pallas")
                          ).minimize(get("rastrigin"), KEY)
    assert np.isfinite(res.value)
    assert res.value < 10.0 * 8 * 2      # far below random-uniform expectation


# --- device-resident engine --------------------------------------------------

@pytest.mark.parametrize("sync_policy", ("barrier", "async"))
def test_device_resident_matches_host_stepped(sync_policy):
    """Same seed -> the single-scan device program and the host-stepped
    BucketStepper (one job, one jit call per round) produce the same
    incumbent trace, final value and evaluation count, bit for bit."""
    cfg = IslandConfig(n_islands=2, pop=16, dim=4, sync_every=5, max_evals=4000,
                       sync_policy=sync_policy)
    opt = IslandOptimizer(ALGORITHMS["de"], cfg)
    r_dev = opt.minimize(SPHERE, KEY)
    stepper = opt.bucket_stepper(SPHERE)
    state, round_keys = stepper.init(KEY[None])
    history = []
    for r in range(stepper.n_rounds):
        state, point = stepper.step(state, round_keys, r)
        history.append(np.asarray(point)[0])
    _, vals = stepper.best(state)
    np.testing.assert_array_equal(np.asarray(r_dev.history), np.asarray(history))
    assert float(vals[0]) == r_dev.value
    assert stepper.evals_done(stepper.n_rounds) == r_dev.n_evals


def test_device_resident_single_host_transfer(monkeypatch):
    """minimize -> results cross host<->device exactly once."""
    pulls = {"n": 0}
    real = jax.device_get

    def counting(x):
        pulls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    cfg = IslandConfig(n_islands=2, pop=16, dim=4, sync_every=5, max_evals=4000)
    res = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
    assert pulls["n"] == 1
    assert np.isfinite(res.value) and len(res.history) > 1


def test_device_resident_history_on_device_buffer():
    cfg = IslandConfig(n_islands=1, pop=16, dim=4, migration="none",
                       max_evals=3200)
    res = IslandOptimizer(ALGORITHMS["de"], cfg).minimize(SPHERE, KEY)
    hist = np.asarray(res.history)
    n_rounds = (cfg.max_evals - 16) // (16 * cfg.sync_every)
    assert hist.shape == (n_rounds,)
    assert np.all(hist[1:] <= hist[:-1] + 1e-9)


# --- fused DE (step_override) ------------------------------------------------

def test_fused_de_one_generation_matches_xla():
    f = get("sphere")
    pop, dim = 24, 16
    ev = make_batch_evaluator(f, ExecutorConfig())
    plain = de.make(f=f, evaluator=ev, pop=pop, dim=dim)
    fused = de.make(f=f, evaluator=ev, pop=pop, dim=dim, fused=True)
    assert fused.step_override is not None and plain.step_override is None
    state = plain.init(jax.random.fold_in(KEY, 1))
    gk = jax.random.fold_in(KEY, 2)
    s_plain = plain.gen(dict(state), gk)
    s_fused = fused.step_override(dict(state), gk)
    np.testing.assert_allclose(s_plain["fit"], s_fused["fit"], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_plain["pop"], s_fused["pop"], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_plain["best_val"], s_fused["best_val"],
                               rtol=1e-4, atol=1e-4)


def test_fused_de_runs_under_island_engine():
    """`de.make(..., fused=True)` under the engine on CPU (interpret mode)."""
    f = get("rastrigin")
    cfg = IslandConfig(n_islands=2, pop=24, dim=8, sync_every=5, max_evals=6000)
    r1 = IslandOptimizer(ALGORITHMS["de"], cfg, params={"fused": True}).minimize(f, KEY)
    r2 = IslandOptimizer(ALGORITHMS["de"], cfg, params={"fused": True}).minimize(f, KEY)
    assert r1.value == r2.value          # deterministic
    assert np.isfinite(r1.value)
    hist = np.asarray(r1.history)
    assert np.all(hist[1:] <= hist[:-1] + 1e-9)
    assert r1.value < 10.0 * 8 * 2


def test_fused_de_shifted_rosenbrock():
    """Fused path honors the CEC'2008 shift/bias carried on the Function."""
    f = make_shifted_rosenbrock(16)
    cfg = IslandConfig(n_islands=1, pop=32, dim=16, migration="none",
                       max_evals=20_000)
    res = IslandOptimizer(ALGORITHMS["de"], cfg,
                          params={"w": 0.5, "px": 0.2, "fused": True}).minimize(f, KEY)
    assert res.value >= 390.0 - 1e-3     # f* = 390 — bias must be applied
    assert res.value < 1e7


def test_fused_de_rejects_best1bin_and_unregistered():
    ev = make_batch_evaluator(SPHERE, ExecutorConfig())
    with pytest.raises(AssertionError):
        de.make(f=SPHERE, evaluator=ev, pop=8, dim=4, fused=True,
                strategy="best1bin")
    wf = get("weierstrass")
    with pytest.raises(KeyError):
        de.make(f=wf, evaluator=make_batch_evaluator(wf, ExecutorConfig()),
                pop=8, dim=4, fused=True)


# --- GC-stable compiled-program cache keys -----------------------------------

def test_fn_token_is_stable_and_never_recycled():
    """fn_token replaces id() in cache keys: stable per live callable, unique
    across callables, and never reused after GC (the id()-recycling hazard
    that could silently serve a stale compiled program)."""
    import gc
    from repro.functions.benchmarks import fn_token

    def f(x):
        return x

    def g(x):
        return x

    assert fn_token(f) == fn_token(f)
    assert fn_token(f) != fn_token(g)
    dead_tok = fn_token(g)
    del g
    gc.collect()

    def h(x):
        return x

    assert fn_token(h) != dead_tok           # monotonic counter, no recycling


def test_cache_token_keys_on_shift_content():
    """Two objectives sharing one callable but carrying different shifts must
    key differently — the id(shift)-reuse case that used to be able to serve
    a program compiled for the wrong shift."""
    import dataclasses
    f1 = make_shifted_rosenbrock(6, seed=1)
    f2 = dataclasses.replace(f1, shift=f1.shift + 1.0)
    assert f1.cache_token() != f2.cache_token()
    assert f1.cache_token() == f1.cache_token()
    # and the evaluator cache respects it: different shifts, different
    # compiled pallas programs (same callable identity either way)
    cfg = ExecutorConfig(backend="pallas")
    e1 = make_batch_evaluator(f1, cfg)
    e2 = make_batch_evaluator(f2, cfg)
    assert e1 is not e2
    assert make_batch_evaluator(f1, cfg) is e1   # and still memoizes


def test_single_optimizer_run_cache_hits_across_calls():
    """IslandOptimizer's per-objective program cache: same Function object ->
    cached jitted run; equal-content clone -> same token class but distinct
    fn identity, so it rebuilds instead of serving the stale closure."""
    f = get("sphere", 4)
    cfg = IslandConfig(n_islands=2, pop=8, dim=4, sync_every=2, max_evals=600)
    opt = IslandOptimizer(ALGORITHMS["de"], cfg)
    r1 = opt.minimize(f, KEY)
    n_cached = len(opt._many_cache)
    r2 = opt.minimize(f, KEY)
    assert len(opt._many_cache) == n_cached  # second call reused the program
    assert r1.value == r2.value


# --- fused PSO/GA/SA + eval_select (ISSUE 6) ---------------------------------

from repro.core import ga, pso, sa  # noqa: E402


@pytest.mark.parametrize("mod,keys", [
    (pso, ("pop", "fit", "vel", "pbest", "pbest_f", "best_val")),
    (ga, ("pop", "fit", "age", "alive", "best_val")),
    (sa, ("pop", "fit", "t", "best_val")),
])
def test_fused_one_generation_matches_xla(mod, keys):
    """Same key, same state -> the fused whole-generation kernel reproduces
    the plain XLA gen bit-for-bit up to f32 summation noise (mirrors the
    fused-DE regression above)."""
    f = get("rastrigin")
    pop, dim = 24, 16
    ev = make_batch_evaluator(f, ExecutorConfig())
    plain = mod.make(f=f, evaluator=ev, pop=pop, dim=dim)
    fused = mod.make(f=f, evaluator=ev, pop=pop, dim=dim, fused=True)
    assert fused.step_override is not None and plain.step_override is None
    state = plain.init(jax.random.fold_in(KEY, 3))
    gk = jax.random.fold_in(KEY, 4)
    s_plain = plain.gen(dict(state), gk)
    s_fused = fused.step_override(dict(state), gk)
    assert set(s_plain) == set(s_fused) >= set(keys)
    for k in keys:
        np.testing.assert_allclose(
            np.asarray(s_plain[k], np.float32), np.asarray(s_fused[k], np.float32),
            rtol=1e-4, atol=1e-4, err_msg=f"{mod.__name__}:{k}")


@pytest.mark.parametrize("algo", ["pso", "ga", "sa"])
def test_fused_policy_runs_under_island_engine(algo):
    f = get("rastrigin")
    cfg = IslandConfig(n_islands=2, pop=24, dim=8, sync_every=5, max_evals=6000)
    r1 = IslandOptimizer(ALGORITHMS[algo], cfg, params={"fused": True}).minimize(f, KEY)
    r2 = IslandOptimizer(ALGORITHMS[algo], cfg, params={"fused": True}).minimize(f, KEY)
    assert r1.value == r2.value          # deterministic
    assert np.isfinite(r1.value)
    hist = np.asarray(r1.history)
    assert np.all(hist[1:] <= hist[:-1] + 1e-9)


def test_fused_portfolio_under_lax_switch():
    """Heterogeneous portfolio where every branch is a fused kernel: the
    step_override path must survive lax.switch tracing and stay deterministic."""
    f = get("rastrigin")
    cfg = IslandConfig(n_islands=3, pop=16, dim=8, sync_every=5, max_evals=4800,
                       portfolio=("de", "pso", "sa"))
    fused_params = {"de": {"fused": True}, "pso": {"fused": True},
                    "sa": {"fused": True}}
    r1 = IslandOptimizer(None, cfg, params=fused_params).minimize(f, KEY)
    r2 = IslandOptimizer(None, cfg, params=fused_params).minimize(f, KEY)
    assert r1.value == r2.value
    assert np.isfinite(r1.value) and r1.value < 10.0 * 8 * 2


def test_executor_kernel_config_threads_to_pallas_backend():
    """ExecutorConfig.kernel pins the eval kernel's tiling; a pinned config
    and the autotuned default must agree numerically."""
    from repro.kernels import KernelConfig
    f = get("rastrigin")
    pop = jax.random.uniform(jax.random.fold_in(KEY, 21), (37, 12),
                             minval=f.lo, maxval=f.hi)
    pinned = make_batch_evaluator(
        f, ExecutorConfig(backend="pallas",
                          kernel=KernelConfig(pop_block=8, dim_pad=128)))(pop)
    auto = make_batch_evaluator(f, ExecutorConfig(backend="pallas"))(pop)
    np.testing.assert_allclose(np.asarray(pinned), np.asarray(auto),
                               rtol=1e-6, atol=1e-6)
