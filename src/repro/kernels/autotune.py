"""Roofline-driven kernel autotuning — shape-specialized Pallas tile configs.

The fused kernels (``bench_eval``, ``de_step``, ``pso_step``, ``ga_step``,
``eval_select``) used to hard-code their tile shapes (``pop_block=8`` for
evaluation, ``128`` for the DE step). This module replaces those constants
with a per-op, shape-specialized config chosen by the analytic memory model
the repo already carries:

  * candidate ``(pop_block, dim_pad)`` configs are scored with the roofline
    terms of ``parallel.roofline`` (compute = FLOPs / peak, memory = HBM
    bytes / bandwidth, from the attached chip's ``DEVICE_PEAKS`` row, as
    the dry-run analyzer uses) built from a per-kernel operand profile
    (``KIND_PROFILES``);
  * VMEM feasibility comes from ``parallel.memmodel.pallas_tile_bytes`` (the
    double-buffered working set of one grid step must fit the budget);
  * off-TPU the kernels run in Pallas *interpret* mode, where every grid
    step costs a host-visible dispatch — the score adds a per-step overhead
    term, so interpret-mode configs converge to few large tiles while TPU
    configs keep tiles VMEM-sized for pipelining;
  * an optional short *measured* sweep (``measure=True``) times the real
    kernel entry over the feasible candidates and overrides the model.

Chosen configs are cached per shape-class — ``(kind, P, D, eval tag, dtype,
platform, interpret)`` — alongside the compiled-program caches the scheduler
keeps, so a shape-class is tuned once per process and every later build is a
cache hit (``tests/test_autotune.py`` enforces no re-tune). Function-keyed
lookups (``choose_for``) key on ``Function.cache_token()`` — the GC-stable
identity used by every other compiled-program cache in the repo — so a
recycled objective ``id()`` can never serve a config tuned for a dead shift.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

import jax

from repro.parallel.memmodel import pallas_tile_bytes
from repro.parallel.roofline import (DEVICE_PEAKS, REHEARSAL_KIND, Roofline,
                                     device_peaks)

# Rates of the rehearsal row, which scores every tile chosen off the TPU.
PEAK_FLOPS_BF16 = DEVICE_PEAKS[REHEARSAL_KIND].flops_bf16
HBM_BW = DEVICE_PEAKS[REHEARSAL_KIND].hbm_bw

# -- the threaded config -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """How the Pallas kernel layer tiles and runs — one config threaded from
    ``ExecutorConfig.kernel`` through every kernel entry point.

    ``None`` fields resolve at call time: ``pop_block``/``dim_pad`` from the
    autotuner (per shape-class, cached), ``interpret`` from the platform
    (interpret mode off-TPU). ``dtype`` is the HBM storage dtype of the
    population tiles (compute is always f32 in-kernel); non-f32 dtypes halve
    memory traffic at a parity-tolerance cost.
    """

    pop_block: int | None = None
    dim_pad: int | None = None
    interpret: bool | None = None
    dtype: str = "float32"

    def itemsize(self) -> int:
        """Bytes per element of the HBM storage dtype."""
        return int(np.dtype(self.dtype).itemsize)


# -- per-kernel operand profiles ---------------------------------------------

@dataclasses.dataclass(frozen=True)
class KindProfile:
    """Operand counts of one fused kernel, per grid step.

    ``vec`` counts ``(pop_block, dim_pad)`` tiles moved between HBM and VMEM
    (inputs + outputs), ``row`` the ``(pop_block,)`` per-row operands,
    ``bcast`` the ``(dim_pad,)`` broadcast rows (shift, gbest), and
    ``var_flops`` the non-evaluation arithmetic per element (variation +
    selection math).
    """

    vec_in: int
    vec_out: int
    row: int = 2
    bcast: int = 1
    var_flops: int = 0


KIND_PROFILES: dict[str, KindProfile] = {
    "bench_eval": KindProfile(vec_in=1, vec_out=0, row=1, bcast=1),
    "de_step": KindProfile(vec_in=5, vec_out=1, row=4, bcast=1, var_flops=7),
    "pso_step": KindProfile(vec_in=5, vec_out=3, row=3, bcast=2, var_flops=11),
    "ga_step": KindProfile(vec_in=5, vec_out=1, row=5, bcast=1, var_flops=8),
    "eval_select": KindProfile(vec_in=2, vec_out=1, row=3, bcast=1,
                               var_flops=2),
}

# Rough per-element FLOP weights of the ``_eval_tile`` bodies (transcendental
# ops counted ~4 flops). Only the *relative* magnitude vs the memory term
# matters for tile choice.
EVAL_FLOPS: dict[str, int] = {
    "sphere": 2, "rastrigin": 12, "rosenbrock": 8, "shifted_rosenbrock": 9,
    "ackley": 14, "griewank": 16, "schwefel": 14, "levy": 22,
    "dropwave": 14, "michalewicz": 24,
}
_DEFAULT_EVAL_FLOPS = 12

# VMEM working-set budget per grid step (double-buffered), bytes. Real TPU
# cores expose ~16 MiB of VMEM; leave headroom for Mosaic's own scratch.
VMEM_BUDGET = 12 * 1024 * 1024
# Host-visible cost of one interpret-mode grid step (the Pallas interpreter
# re-enters per step); measured ~tens of microseconds on this container.
INTERPRET_STEP_OVERHEAD_S = 2e-5
# Candidate tile heights swept by the model.
POP_BLOCKS = (8, 16, 32, 64, 128, 256, 512, 1024)


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= ``n``."""
    return -(-n // mult) * mult


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` flag: explicit value, else off-TPU auto."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class Prediction:
    """One scored candidate: its roofline terms plus the tiling metadata the
    score adds on top (grid steps, VMEM working set, total predicted time)."""

    pop_block: int
    dim_pad: int
    roofline: Roofline
    n_grid: int
    tile_bytes: int
    t_total: float

    @property
    def feasible(self) -> bool:
        """Whether the double-buffered tile working set fits the VMEM budget."""
        return self.tile_bytes <= VMEM_BUDGET


def predict(kind: str, P: int, D: int, pop_block: int, dim_pad: int,
            tag: str = "sphere", itemsize: int = 4,
            interpret: bool = False) -> Prediction:
    """Roofline prediction for one ``(pop_block, dim_pad)`` candidate.

    FLOPs and HBM bytes come from the kernel's operand profile over the
    padded ``(Pp, dim_pad)`` problem; time terms use the attached chip's row
    of ``parallel.roofline.DEVICE_PEAKS`` (the v5e row off the TPU), as
    ``parallel.roofline.analyze`` does. Interpret mode adds a per-grid-step
    dispatch overhead, which is what drives off-TPU configs toward one big
    tile while VMEM keeps TPU tiles small.
    """
    prof = KIND_PROFILES[kind]
    Pp = round_up(P, pop_block)
    n_grid = Pp // pop_block
    eflops = EVAL_FLOPS.get(tag, _DEFAULT_EVAL_FLOPS)
    elems = Pp * dim_pad
    flops = float(elems) * (prof.var_flops + eflops)
    hbm = float(
        (prof.vec_in + prof.vec_out) * elems * itemsize
        + prof.row * Pp * 4
        + prof.bcast * dim_pad * 4
    )
    peaks = device_peaks()
    t_c = flops / peaks.flops_bf16
    t_m = hbm / peaks.hbm_bw
    tile = pallas_tile_bytes(
        prof.vec_in + prof.vec_out, pop_block, dim_pad,
        n_row=prof.row, n_bcast=prof.bcast, itemsize=4,  # VMEM tiles are f32
        double_buffered=True)
    roof = Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=0.0, t_compute=t_c,
        t_memory=t_m, t_collective=0.0,
        bottleneck="compute" if t_c >= t_m else "memory",
        peak_bytes=float(tile))
    t = max(t_c, t_m)
    if interpret:
        t += n_grid * INTERPRET_STEP_OVERHEAD_S
    return Prediction(pop_block=pop_block, dim_pad=dim_pad, roofline=roof,
                      n_grid=n_grid, tile_bytes=tile, t_total=t)


def candidates(P: int, D: int) -> list[tuple[int, int]]:
    """The ``(pop_block, dim_pad)`` grid the tuner scores: tile heights up to
    the padded population, lane-aligned dim paddings (the minimal 128-multiple
    and the next one up, so padding waste is scored rather than assumed)."""
    d0 = round_up(max(D, 1), 128)
    dims = [d0] if d0 > D + 128 else [d0, d0 + 128]
    pmax = round_up(max(P, 1), 8)
    blocks = sorted({min(b, pmax) for b in POP_BLOCKS})
    return [(b, d) for b in blocks for d in dims]


# -- the per-shape-class config cache ----------------------------------------

_CACHE: dict[tuple, KernelConfig] = {}
_FN_CACHE: dict[tuple, KernelConfig] = {}
_STATS = {"hits": 0, "misses": 0, "measured": 0}


def cache_stats() -> dict[str, int]:
    """Tuner cache counters (hits / misses / measured sweeps) — test hook."""
    return dict(_STATS)


def clear_cache() -> None:
    """Drop every cached config and reset counters (tests only)."""
    _CACHE.clear()
    _FN_CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0


def _shape_class(kind: str, P: int, D: int, tag: str, dtype: str,
                 interpret: bool, measure: bool) -> tuple:
    return (kind, P, D, tag, dtype, jax.default_backend(), interpret, measure)


def choose(kind: str, P: int, D: int, tag: str = "sphere", *,
           dtype: str = "float32", interpret: bool | None = None,
           measure: bool = False) -> KernelConfig:
    """The autotuned, fully-resolved :class:`KernelConfig` for one kernel
    shape-class.

    Scores every feasible ``(pop_block, dim_pad)`` candidate with
    :func:`predict` (optionally re-ranking the top candidates by a short
    measured sweep) and caches the winner per shape-class, so repeated builds
    — scheduler bucket flushes, benchmark loops, re-traces — never re-tune.
    """
    if kind not in KIND_PROFILES:
        raise KeyError(
            f"unknown kernel kind {kind!r}; known: {sorted(KIND_PROFILES)}")
    interp = default_interpret(interpret)
    key = _shape_class(kind, P, D, tag, dtype, interp, measure)
    hit = _CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        return hit
    _STATS["misses"] += 1
    itemsize = int(np.dtype(dtype).itemsize)
    preds = [predict(kind, P, D, b, d, tag=tag, itemsize=itemsize,
                     interpret=interp) for (b, d) in candidates(P, D)]
    feasible = [p for p in preds if p.feasible] or preds  # degenerate: best-effort
    feasible.sort(key=lambda p: (p.t_total, p.tile_bytes))
    best = feasible[0]
    if measure:
        best = _measured_best(kind, P, D, tag, feasible[:4], interp, dtype)
        _STATS["measured"] += 1
    cfg = KernelConfig(pop_block=best.pop_block, dim_pad=best.dim_pad,
                       interpret=interp, dtype=dtype)
    _CACHE[key] = cfg
    return cfg


def choose_for(f, kind: str, P: int, D: int, *,
               dtype: str = "float32", interpret: bool | None = None,
               measure: bool = False) -> KernelConfig:
    """:func:`choose` keyed by an objective's ``Function.cache_token()``.

    The maker-level entry (``de.make(fused=True)`` and friends) tunes against
    the *objective*, not a bare tag string; keying the memo on the GC-stable
    ``cache_token`` (not ``id(f)``) mirrors the executor/scheduler program
    caches, so a recycled object address can never alias a dead objective's
    config.
    """
    from repro.kernels import registry as kreg  # late: avoid import cycles
    interp = default_interpret(interpret)
    key = (kind, P, D, dtype, jax.default_backend(), interp, measure,
           *f.cache_token())
    hit = _FN_CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        return hit
    tag = kreg.get_spec(f.name).eval_tag
    cfg = choose(kind, P, D, tag, dtype=dtype, interpret=interpret,
                 measure=measure)
    _FN_CACHE[key] = cfg
    return cfg


def merge(cfg: KernelConfig | None, *, pop_block: int | None = None,
          dim_pad: int | None = None,
          interpret: bool | None = None) -> KernelConfig:
    """Overlay explicit per-call keyword overrides onto a (possibly ``None``)
    threaded config — explicit keywords win, then config fields, then the
    tuner fills whatever is still ``None`` via :func:`resolve`."""
    base = cfg if cfg is not None else KernelConfig()
    return dataclasses.replace(
        base,
        pop_block=pop_block if pop_block is not None else base.pop_block,
        dim_pad=dim_pad if dim_pad is not None else base.dim_pad,
        interpret=interpret if interpret is not None else base.interpret)


def resolve(cfg: KernelConfig | None, kind: str, P: int, D: int,
            tag: str = "sphere", interpret: bool | None = None,
            measure: bool = False) -> KernelConfig:
    """Fill a (possibly partial) :class:`KernelConfig` into a fully-resolved
    one — explicit fields win, missing fields come from the tuner cache.

    Every kernel entry point funnels through here, so a config threaded via
    ``ExecutorConfig.kernel`` reaches ``bench_eval``/``de_step``/``pso_step``
    /``ga_step``/``eval_select`` uniformly instead of each call site keeping
    its own keyword default.
    """
    cfg = cfg if cfg is not None else KernelConfig()
    interp = cfg.interpret if cfg.interpret is not None else interpret
    if cfg.pop_block is not None and cfg.dim_pad is not None:
        return KernelConfig(pop_block=cfg.pop_block, dim_pad=cfg.dim_pad,
                            interpret=default_interpret(interp),
                            dtype=cfg.dtype)
    tuned = choose(kind, P, D, tag, dtype=cfg.dtype, interpret=interp,
                   measure=measure)
    return KernelConfig(
        pop_block=cfg.pop_block if cfg.pop_block is not None else tuned.pop_block,
        dim_pad=cfg.dim_pad if cfg.dim_pad is not None else tuned.dim_pad,
        interpret=tuned.interpret, dtype=cfg.dtype)


# -- optional measured sweep -------------------------------------------------

def _measured_best(kind: str, P: int, D: int, tag: str,
                   preds: list[Prediction], interpret: bool,
                   dtype: str) -> Prediction:
    """Re-rank the model's top candidates by a short timed sweep of the real
    kernel entry (3 reps, best-of). Falls back to the model's pick when the
    kernel cannot run (e.g. unregistered tag in a unit test)."""
    try:
        runner = _make_runner(kind, P, D, tag, dtype)
    except Exception:
        return preds[0]
    best, best_t = preds[0], float("inf")
    for p in preds:
        try:
            t = _time_once(lambda: runner(p.pop_block, p.dim_pad, interpret))
        except Exception:
            continue
        if t < best_t:
            best, best_t = p, t
    return best


def _time_once(fn: Callable[[], None], reps: int = 3) -> float:
    fn()  # warm-up / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _make_runner(kind: str, P: int, D: int, tag: str, dtype: str):
    """A closure running one real kernel invocation on synthetic data."""
    import jax.numpy as jnp

    # Module imports, not package attributes: the package re-exports the entry
    # *functions* under the same names, which would shadow the modules here.
    import repro.kernels.bench_eval as _be
    import repro.kernels.de_step as _de
    import repro.kernels.eval_select as _es
    import repro.kernels.ga_step as _ga
    import repro.kernels.pso_step as _ps

    key = jax.random.PRNGKey(0)
    pop = jax.random.uniform(key, (P, D), minval=-1.0, maxval=1.0)
    fit = jnp.ones((P,), jnp.float32)

    def cfgk(b: int, d: int, interp: bool) -> KernelConfig:
        return KernelConfig(pop_block=b, dim_pad=d, interpret=interp,
                            dtype=dtype)

    if kind == "bench_eval":
        def run(b, d, interp):
            _be.bench_eval(pop, tag, kernel_cfg=cfgk(b, d, interp)
                           ).block_until_ready()
    elif kind == "eval_select":
        def run(b, d, interp):
            _es.eval_select(pop, fit, pop, fn=tag,
                            kernel_cfg=cfgk(b, d, interp)
                            )[1].block_until_ready()
    elif kind == "de_step":
        i = jnp.arange(P)
        idx = jnp.stack([(i + 1) % P, (i + 2) % P, (i + 3) % P])
        u = jnp.zeros((P, D), jnp.float32)
        jr = jnp.zeros((P,), jnp.int32)

        def run(b, d, interp):
            _de.de_step(pop, fit, idx, u, jr, fn=tag,
                        kernel_cfg=cfgk(b, d, interp))[1].block_until_ready()
    elif kind == "pso_step":
        z = jnp.zeros_like(pop)

        def run(b, d, interp):
            _ps.pso_step(pop, z, pop, fit, z, z, pop[0], fn=tag,
                         kernel_cfg=cfgk(b, d, interp))[2].block_until_ready()
    elif kind == "ga_step":
        z = jnp.zeros_like(pop)
        cut = jnp.ones((P,), jnp.int32)
        co = jnp.zeros((P,), jnp.float32)

        def run(b, d, interp):
            _ga.ga_step(pop, pop, pop, fit, cut, co, z, z, fn=tag,
                        kernel_cfg=cfgk(b, d, interp))[1].block_until_ready()
    else:  # pragma: no cover - guarded by KIND_PROFILES check in choose()
        raise KeyError(kind)
    return run
