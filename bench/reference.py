"""Plain reference of what the optimizer computes, for the benchmark's check.

Written from the published algorithms and the engine's documented fixed-seed
contract (DESIGN.md §4, §5, §10, §6), in straightforward ``jax.numpy``: no
kernels, no batching over jobs, no sharding. It imports nothing of the program
under test and takes nothing the program made; the shift vector of the CEC'2008
F3 objective is drawn here from its own seed.

``make_replay(cls, rounds, dtype)`` runs the first ``rounds`` sync rounds of a
request from its seed's key and returns the incumbent after each, which is what
the program reports as a job's per-round history, and the best point.
``dtype=jnp.bfloat16`` is the control: the same algorithm one precision below
the float32 that every configuration states.
``evaluate64`` recomputes an answer's value in float64 on the host.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# name -> (lo, hi) box of the classical definitions (CEC'2008 F3: [-100, 100])
BOXES = {
    "sphere": (-100.0, 100.0),
    "rastrigin": (-5.12, 5.12),
    "ackley": (-32.768, 32.768),
    "griewank": (-600.0, 600.0),
    "shifted_rosenbrock": (-100.0, 100.0),
}
F3_SHIFT_SEED, F3_SHIFT_BOX, F3_BIAS = 2008, (-90.0, 90.0), 390.0


def f3_shift(dim: int) -> np.ndarray:
    """CEC'2008 F3 shift vector o, uniform in [-90, 90) from seed 2008."""
    return np.asarray(jax.random.uniform(
        jax.random.PRNGKey(F3_SHIFT_SEED), (dim,), minval=F3_SHIFT_BOX[0],
        maxval=F3_SHIFT_BOX[1], dtype=F32))


def objective(name: str, dim: int, dt=F32) -> Callable:
    """``x (..., dim) -> (...)`` in dtype ``dt``."""
    if name == "sphere":
        return lambda x: jnp.sum(x * x, axis=-1)
    if name == "rastrigin":
        return lambda x: 10.0 * dim + jnp.sum(
            x * x - 10.0 * jnp.cos(2.0 * jnp.pi * x), axis=-1)
    if name == "ackley":
        return lambda x: (-20.0 * jnp.exp(-0.2 * jnp.sqrt(jnp.mean(x * x, -1)))
                          - jnp.exp(jnp.mean(jnp.cos(2.0 * jnp.pi * x), -1))
                          + 20.0 + jnp.e).astype(x.dtype)
    if name == "griewank":
        i = jnp.arange(1, dim + 1, dtype=dt)
        return lambda x: (jnp.sum(x * x, -1) / 4000.0
                          - jnp.prod(jnp.cos(x / jnp.sqrt(i)), -1) + 1.0)
    if name == "shifted_rosenbrock":
        o = jnp.asarray(f3_shift(dim), dt)

        def f3(x):
            z = x - o + 1.0
            z0, z1 = z[..., :-1], z[..., 1:]
            return (jnp.sum(100.0 * (z1 - z0 * z0) ** 2 + (1.0 - z0) ** 2, -1)
                    + jnp.asarray(F3_BIAS, x.dtype))
        return f3
    raise KeyError(name)


def evaluate64(name: str, x) -> float:
    """The objective at one point, in float64 on the host."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    if name == "sphere":
        return float(np.sum(x * x))
    if name == "rastrigin":
        return float(10.0 * d + np.sum(x * x - 10.0 * np.cos(2 * np.pi * x)))
    if name == "ackley":
        return float(-20.0 * np.exp(-0.2 * np.sqrt(np.mean(x * x)))
                     - np.exp(np.mean(np.cos(2 * np.pi * x))) + 20.0 + np.e)
    if name == "griewank":
        i = np.arange(1, d + 1, dtype=np.float64)
        return float(np.sum(x * x) / 4000.0 - np.prod(np.cos(x / np.sqrt(i)))
                     + 1.0)
    if name == "shifted_rosenbrock":
        z = x - f3_shift(d).astype(np.float64) + 1.0
        return float(np.sum(100.0 * (z[1:] - z[:-1] ** 2) ** 2
                            + (1.0 - z[:-1]) ** 2) + F3_BIAS)
    raise KeyError(name)


# -- budget ------------------------------------------------------------------

def polish_evals_per_point(dim: int, steps: int, n_ladder: int = 8) -> int:
    """ASD: per step one 4th-order Richardson gradient (4*dim probes) and a
    line-search ladder of ``n_ladder`` trial steps."""
    return steps * (4 * dim + n_ladder)


def budget(cls: dict) -> tuple[int, int]:
    """(sync rounds, evaluations) a request's ``max_evals`` buys: every island
    evaluates its population once at init and once per generation (chunked DE:
    its blocks' rows); a polish
    event every ``polish_every`` rounds costs ``topk`` points per island. The
    number of rounds is the largest whose cost fits the budget, at least 1."""
    pop, isl, every = cls["pop"], cls["n_islands"], cls["sync_every"]
    init = pop * isl
    per_round = sum(_evals_per_gen(cls, n) for n in policies_of(cls)) * every
    per_polish, pe = 0, 1
    if cls.get("polish", "none") != "none":
        per_polish = (polish_evals_per_point(cls["dim"], cls["polish_steps"])
                      * min(cls["polish_topk"], pop) * isl)
        pe = max(1, cls["polish_every"])
    left = cls["max_evals"] - init
    n = max(1, left // per_round)
    while n > 1 and n * per_round + (n // pe) * per_polish > left:
        n -= 1
    return n, init + n * per_round + (n // pe) * per_polish


# -- policies ----------------------------------------------------------------

def _evals_per_gen(cls: dict, name: str) -> int:
    """Chunked DE evaluates 8 equal blocks of ceil-free size pop // 8, so a
    population that 8 does not divide costs the blocks' rows, not pop."""
    pop = cls["pop"]
    if name == "de" and _params_of(cls, name).get("barrier_mode") == "chunked":
        csz = max(1, pop // 8)
        return csz * -(-pop // csz)
    return pop


def _box(cls):
    return BOXES[cls["fn"]]


def _track(s, pop, fit):
    i = jnp.argmin(fit)
    better = fit[i] < s["best_val"]
    return {**s, "pop": pop, "fit": fit,
            "best_val": jnp.where(better, fit[i], s["best_val"]),
            "best_arg": jnp.where(better, pop[i], s["best_arg"])}


def _evaluator(f):
    def ev(x):
        y = f(x)
        return jnp.where(jnp.isfinite(y), y, jnp.inf)
    return ev


def _uniform_pop(key, pop, dim, lo, hi, dt):
    return jax.random.uniform(key, (pop, dim), minval=lo, maxval=hi,
                              dtype=F32).astype(dt)


def _de(cls, params, ev, dt):
    """DE/rand/1/bin (or best/1/bin): mutant a + w (b - c) from three donors
    that differ from the target row, binomial crossover with rate px and one
    forced coordinate, greedy selection (a trial wins ties). ``chunked``
    replaces the population in 8 blocks, later blocks reading the earlier
    blocks' new rows."""
    pop, dim = cls["pop"], cls["dim"]
    lo, hi = _box(cls)
    w, px = params.get("w", 0.5), params.get("px", 0.2)
    strategy = params.get("strategy", "rand1bin")
    chunked = params.get("barrier_mode", "sync") == "chunked"
    csz = max(1, pop // 8) if chunked else pop
    n_chunks = -(-pop // csz)

    def init(key):
        p = _uniform_pop(key, pop, dim, lo, hi, dt)
        fit = ev(p)
        i = jnp.argmin(fit)
        return {"pop": p, "fit": fit, "best_arg": p[i], "best_val": fit[i]}

    def trials(p, best, key):
        ksel, kcr, kj = jax.random.split(key, 3)
        k1, k2, k3 = jax.random.split(ksel, 3)
        i = jnp.arange(pop)
        ra, rb, rc = ((i + 1 + jax.random.randint(k, (pop,), 0, pop - 1)) % pop
                      for k in (k1, k2, k3))
        base = p[ra] if strategy == "rand1bin" else best[None, :]
        mutant = base + jnp.asarray(w, dt) * (p[rb] - p[rc])
        cross = jax.random.uniform(kcr, (pop, dim)) < px
        jrand = jax.random.randint(kj, (pop,), 0, dim)
        cross = cross | (jnp.arange(dim)[None, :] == jrand[:, None])
        return jnp.clip(jnp.where(cross, mutant, p), lo, hi)

    def gen(s, key):
        p, fit = s["pop"], s["fit"]
        if not chunked:
            t = trials(p, s["best_arg"], key)
            tf = ev(t)
            win = tf <= fit
            return _track(s, jnp.where(win[:, None], t, p),
                          jnp.where(win, tf, fit))

        def chunk(c, carry):
            p, fit = carry
            t = trials(p, p[jnp.argmin(fit)], jax.random.fold_in(key, c))
            start = jnp.minimum(c * csz, pop - csz)
            t = jax.lax.dynamic_slice_in_dim(t, start, csz)
            cp = jax.lax.dynamic_slice_in_dim(p, start, csz)
            cf = jax.lax.dynamic_slice_in_dim(fit, start, csz)
            tf = ev(t)
            win = tf <= cf
            p = jax.lax.dynamic_update_slice_in_dim(
                p, jnp.where(win[:, None], t, cp), start, 0)
            fit = jax.lax.dynamic_update_slice_in_dim(
                fit, jnp.where(win, tf, cf), start, 0)
            return p, fit

        p, fit = jax.lax.fori_loop(0, n_chunks, chunk, (p, fit))
        return _track(s, p, fit)

    return init, gen


def _pso(cls, params, ev, dt):
    """Global-best PSO: v <- w v + fp r1 (pbest - x) + fg r2 (gbest - x),
    |v| <= vmax = 0.2 (hi - lo), x <- clip(x + v); personal bests replaced on
    strict improvement."""
    pop, dim = cls["pop"], cls["dim"]
    lo, hi = _box(cls)
    w, fp, fg = params.get("w", 0.6), params.get("fp", 1.0), params.get("fg", 1.0)
    vmax = params.get("vmax_frac", 0.2) * (hi - lo)

    def init(key):
        kx, kv = jax.random.split(key)
        x = _uniform_pop(kx, pop, dim, lo, hi, dt)
        v = (vmax * (jax.random.uniform(kv, (pop, dim)) - 0.5)).astype(dt)
        fit = ev(x)
        i = jnp.argmin(fit)
        return {"pop": x, "fit": fit, "vel": v, "pbest": x, "pbest_f": fit,
                "best_arg": x[i], "best_val": fit[i]}

    def gen(s, key):
        x = s["pop"]
        k1, k2 = jax.random.split(key)
        r1 = jax.random.uniform(k1, (pop, dim)).astype(dt)
        r2 = jax.random.uniform(k2, (pop, dim)).astype(dt)
        v = (w * s["vel"] + fp * r1 * (s["pbest"] - x)
             + fg * r2 * (s["best_arg"] - x))
        v = jnp.clip(v, -vmax, vmax)
        x = jnp.clip(x + v, lo, hi)
        fit = ev(x)
        imp = fit < s["pbest_f"]
        pb = jnp.where(imp[:, None], x, s["pbest"])
        pbf = jnp.where(imp, fit, s["pbest_f"])
        i = jnp.argmin(pbf)
        better = pbf[i] < s["best_val"]
        return {**s, "pop": x, "fit": fit, "vel": v, "pbest": pb, "pbest_f": pbf,
                "best_val": jnp.where(better, pbf[i], s["best_val"]),
                "best_arg": jnp.where(better, pb[i], s["best_arg"])}

    return init, gen


def _sa(cls, params, ev, dt):
    """Parallel Metropolis chains: Gaussian proposal of sigma 0.1 (hi - lo),
    linear cooling T = T0 max(1 - t / n, 0), accept when dF <= 0 or
    u < exp(-dF / T)."""
    pop, dim = cls["pop"], cls["dim"]
    lo, hi = _box(cls)
    T0, n = params.get("T0", 1000.0), float(params.get("n_gens_hint", 10_000))
    sigma = params.get("step_frac", 0.1) * (hi - lo)

    def init(key):
        x = _uniform_pop(key, pop, dim, lo, hi, dt)
        fit = ev(x)
        i = jnp.argmin(fit)
        return {"pop": x, "fit": fit, "t": jnp.zeros((), F32),
                "best_arg": x[i], "best_val": fit[i]}

    def gen(s, key):
        x, fx, t = s["pop"], s["fit"], s["t"]
        kp, ka = jax.random.split(key)
        T = T0 * jnp.maximum(1.0 - t / n, 0.0)
        y = jnp.clip(x + sigma * jax.random.normal(kp, (pop, dim)).astype(dt),
                     lo, hi)
        fy = ev(y)
        dF = fy - fx
        u = jax.random.uniform(ka, (pop,))
        acc = (dF <= 0) | (u < jnp.exp(-dF / jnp.maximum(T, 1e-12)))
        x = jnp.where(acc[:, None], y, x)
        fx = jnp.where(acc, fy, fx)
        i = jnp.argmin(fx)
        better = fx[i] < s["best_val"]
        return {**s, "pop": x, "fit": fx, "t": t + 1.0,
                "best_val": jnp.where(better, fx[i], s["best_val"]),
                "best_arg": jnp.where(better, x[i], s["best_arg"])}

    return init, gen


POLICIES = {"de": _de, "pso": _pso, "sa": _sa}


def _asd_polish(cls, ev, dt):
    """ASD on each island's ``polish_topk`` best points: ``polish_steps``
    steps, each a 4th-order Richardson gradient (h = 1e-4) and an Armijo
    ladder t = beta^j, j < 8 (beta 0.5, slope 1e-4) along -g / |g|, taking the
    largest admissible step, else the best trial, and never moving uphill."""
    lo, hi = _box(cls)
    k, steps, h, L = cls["polish_topk"], cls["polish_steps"], 1e-4, 8
    ts = 0.5 ** jnp.arange(L, dtype=dt)

    def grad(x):
        K, D = x.shape
        e = jnp.eye(D, dtype=dt)
        pr = jnp.concatenate([x[:, None] + h * e, x[:, None] - h * e,
                              x[:, None] + 2 * h * e, x[:, None] - 2 * h * e], 1)
        v = ev(pr.reshape(K * 4 * D, D)).reshape(K, 4, D)
        return (8.0 * (v[:, 0] - v[:, 1]) - (v[:, 2] - v[:, 3])) / (12.0 * h)

    def step(x, fx):
        K, D = x.shape
        g = grad(x)
        d = -g
        dn = d / jnp.maximum(jnp.linalg.norm(d, axis=-1, keepdims=True), 1e-30)
        gd = jnp.sum(g * dn, -1)
        cand = jnp.clip(x[:, None] + ts[None, :, None] * dn[:, None], lo, hi)
        fc = ev(cand.reshape(K * L, D)).reshape(K, L)
        ok = fc <= fx[:, None] + 1e-4 * ts[None] * gd[:, None]
        j = jnp.where(ok.any(1), jnp.argmax(ok, 1), jnp.argmin(fc, 1))
        xj = cand[jnp.arange(K), j]
        fj = fc[jnp.arange(K), j]
        better = fj < fx
        return jnp.where(better[:, None], xj, x), jnp.where(better, fj, fx)

    def polish(s):
        pop, fit = s["pop"], s["fit"]
        idx = jnp.argsort(fit, stable=True)[:k]
        xs, fs = pop[idx], fit[idx]
        x2, f2 = xs, fs
        for _ in range(steps):
            x2, f2 = step(x2, f2)
        better = f2 < fs
        pop = pop.at[idx].set(jnp.where(better[:, None], x2, xs))
        fit = fit.at[idx].set(jnp.where(better, f2, fs))
        return _track(s, pop, fit)

    return polish


def _ring(islands, k):
    """Island i sends its k best to island i + 1 (mod I); each island replaces
    its k worst where a migrant is strictly better. Returns the new islands and
    per island the mask of rows that changed."""
    migs = []
    for s in islands:
        best = jnp.argsort(s["fit"], stable=True)[:k]
        migs.append((s["pop"][best], s["fit"][best]))
    out, masks = [], []
    n = len(islands)
    for i, s in enumerate(islands):
        mp, mf = migs[(i - 1) % n]
        worst = jnp.argsort(s["fit"], stable=True)[-k:]
        cur = s["fit"][worst]
        take = mf < cur
        pop = s["pop"].at[worst].set(jnp.where(take[:, None], mp, s["pop"][worst]))
        fit = s["fit"].at[worst].set(jnp.where(take, mf, cur))
        masks.append(jnp.any(pop != s["pop"], -1) | (fit != s["fit"]))
        out.append({**s, "pop": pop, "fit": fit})
    return out, masks


def _adopt(name, s, mask):
    """An adopted migrant restarts a PSO particle at rest, with its personal
    best at the migrant; DE and SA carry no per-row state to reset."""
    if name != "pso":
        return s
    return {**s, "vel": jnp.where(mask[:, None], 0.0, s["vel"]).astype(s["vel"].dtype),
            "pbest": jnp.where(mask[:, None], s["pop"], s["pbest"]),
            "pbest_f": jnp.where(mask, s["fit"], s["pbest_f"])}


def policies_of(cls: dict) -> list[str]:
    """Per-island policy names: the portfolio cycled over the islands, else the
    one algorithm everywhere."""
    port = cls.get("portfolio") or []
    n = cls["n_islands"]
    if port:
        return [port[i % len(port)] for i in range(n)]
    return [cls.get("algo", "de")] * n


def _params_of(cls: dict, name: str) -> dict:
    p = dict(cls.get("params") or {})
    if cls.get("portfolio"):
        return dict(p.get(name, {}))
    return p


def make_replay(cls: dict, rounds: int, dt=F32) -> Callable:
    """Jitted ``key -> (history (rounds,), arg (dim,))``: the incumbent after
    each of the first sync rounds of request class ``cls`` (its fields as an
    ``OptRequest`` names them) and the best point at the end, following the
    engine's fixed-seed contract: ``key, ik = split(key)``; island keys
    ``split(ik, I)``; round keys from the chain
    ``key, rk = split(key)``; ``split(rk, sync_every)`` generation keys, each
    split over the islands; then ring migration, then polish on its cadence."""
    ev = _evaluator(objective(cls["fn"], cls["dim"], dt))
    names = policies_of(cls)
    n_isl = cls["n_islands"]
    kinds = {n: POLICIES[n](cls, _params_of(cls, n), ev, dt) for n in set(names)}
    polish = _asd_polish(cls, ev, dt) if cls.get("polish", "none") == "asd" else None
    every = max(1, cls.get("polish_every", 1))
    ring = n_isl > 1 and cls.get("migration", "ring") == "ring"
    if cls.get("polish", "none") not in ("none", "asd"):
        raise NotImplementedError(cls["polish"])
    if n_isl > 1 and cls.get("migration", "ring") not in ("ring", "none"):
        raise NotImplementedError(cls["migration"])

    def run(key):
        key, ik = jax.random.split(key)
        iks = jax.random.split(ik, n_isl) if n_isl > 1 else [ik]
        isl = [kinds[n][0](iks[i]) for i, n in enumerate(names)]
        hist = []
        for _ in range(rounds):
            ks = jax.random.split(key)
            key, rk = ks[0], ks[1]

            def one_gen(isl, gk):
                gks = jax.random.split(gk, n_isl) if n_isl > 1 else [gk]
                return [kinds[n][1](isl[i], gks[i])
                        for i, n in enumerate(names)], None

            isl, _ = jax.lax.scan(one_gen, isl,
                                  jax.random.split(rk, cls["sync_every"]))
            if ring:
                isl, masks = _ring(isl, cls.get("n_migrants", 2))
                isl = [_adopt(n, isl[i], masks[i]) for i, n in enumerate(names)]
            if polish is not None and (len(hist) + 1) % every == 0:
                isl = [polish(s) for s in isl]
            hist.append(jnp.min(jnp.stack([s["best_val"].astype(F32)
                                           for s in isl])))
        best = jnp.argmin(jnp.stack([s["best_val"].astype(F32) for s in isl]))
        arg = jnp.stack([s["best_arg"].astype(F32) for s in isl])[best]
        return jnp.stack(hist), arg

    return jax.jit(run)


def rel_gap(a, b) -> float:
    """Largest |a - b| / max(|b|, 1) over two equal-length sequences."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return math.inf
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))
