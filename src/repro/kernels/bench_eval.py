"""Fused population evaluation — Pallas TPU kernel.

The paper's hot loop: every meta-heuristic spends its 1M-evaluation budget in
``f(pop)`` (Fig. 4 protocol). This kernel evaluates a (pop_block, dim) tile per
grid step entirely in VMEM — one HBM read of the population, no intermediate
arrays — for the §V testbed functions listed in ``kernels.registry`` (sphere /
rastrigin / rosenbrock / ackley / griewank / schwefel / levy / dropwave /
michalewicz, incl. the CEC'2008 shifted Rosenbrock via a shift operand).

dim is carried whole per tile (the paper's 1000-D padded to 1024 lane-aligned).
Tile shapes are no longer hard-coded: ``kernels.autotune`` picks
``(pop_block, dim_pad)`` per shape-class from the roofline model (explicit
``pop_block=``/``KernelConfig`` fields still win). Rows added by the
``pop_block`` round-up are masked to **+inf fitness inside the kernel** — pad
rows can never win a downstream selection, rather than relying on the caller
slicing them off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune
from repro.kernels.autotune import KernelConfig

# Objective bodies _eval_tile implements. ``kernels.registry`` maps function
# *names* to one of these tags (several names may share a tag); this tuple is
# the ground truth for what the kernel itself can evaluate.
EVAL_TAGS = (
    "sphere", "rastrigin", "rosenbrock", "ackley", "shifted_rosenbrock",
    "griewank", "schwefel", "levy", "dropwave", "michalewicz",
)


def _eval_tile(x: jax.Array, fn: str, dim: int, bias: float) -> jax.Array:
    """x: (P, Dp) f32 with zero padding beyond ``dim``; returns (P,)."""
    Dp = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = lane < dim
    if fn in ("rosenbrock", "shifted_rosenbrock"):
        if fn == "shifted_rosenbrock":
            x = jnp.where(valid, x + 1.0, 0.0)   # z = x - o + 1 (o applied outside)
        x0 = x
        x1 = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
        pair = lane < (dim - 1)
        t = jnp.where(pair, 100.0 * (x1 - x0 * x0) ** 2 + (1.0 - x0) ** 2, 0.0)
        return t.sum(axis=1) + bias
    if fn == "sphere":
        return jnp.where(valid, x * x, 0.0).sum(axis=1) + bias
    if fn == "rastrigin":
        t = jnp.where(valid, x * x - 10.0 * jnp.cos(2.0 * jnp.pi * x) + 10.0, 0.0)
        return t.sum(axis=1) + bias
    if fn == "ackley":
        s1 = jnp.where(valid, x * x, 0.0).sum(axis=1) / dim
        s2 = jnp.where(valid, jnp.cos(2.0 * jnp.pi * x), 0.0).sum(axis=1) / dim
        return (-20.0 * jnp.exp(-0.2 * jnp.sqrt(s1)) - jnp.exp(s2)
                + 20.0 + jnp.e + bias)
    if fn == "griewank":
        s = jnp.where(valid, x * x, 0.0).sum(axis=1) / 4000.0
        i = jnp.sqrt((lane + 1).astype(jnp.float32))
        c = jnp.where(valid, jnp.cos(x / i), 1.0)
        # Mosaic has no reduce_prod: |prod c| = exp(sum log|c|), and the sign
        # is the parity of the count of negative factors.
        n_neg = jnp.where(c < 0.0, 1.0, 0.0).sum(axis=1)
        mag = jnp.exp(jnp.log(jnp.abs(c)).sum(axis=1))
        p = jnp.where(n_neg - 2.0 * jnp.floor(0.5 * n_neg) > 0.5, -mag, mag)
        return s - p + 1.0 + bias
    if fn == "schwefel":
        t = jnp.where(valid, x * jnp.sin(jnp.sqrt(jnp.abs(x))), 0.0)
        return 418.9829 * dim - t.sum(axis=1) + bias
    if fn == "levy":
        w = 1.0 + (x - 1.0) / 4.0
        first = lane == 0
        mid = lane < (dim - 1)
        last = lane == (dim - 1)
        t1 = jnp.where(first, jnp.sin(jnp.pi * w) ** 2, 0.0).sum(axis=1)
        t2 = jnp.where(
            mid,
            (w - 1.0) ** 2 * (1.0 + 10.0 * jnp.sin(jnp.pi * w + 1.0) ** 2),
            0.0,
        ).sum(axis=1)
        t3 = jnp.where(
            last, (w - 1.0) ** 2 * (1.0 + jnp.sin(2.0 * jnp.pi * w) ** 2), 0.0
        ).sum(axis=1)
        return t1 + t2 + t3 + bias
    if fn == "dropwave":
        s = jnp.where(valid, x * x, 0.0).sum(axis=1)
        return -(1.0 + jnp.cos(12.0 * jnp.sqrt(s))) / (0.5 * s + 2.0) + bias
    if fn == "michalewicz":
        i = (lane + 1).astype(jnp.float32)
        t = jnp.sin(x) * jnp.sin(i * x * x / jnp.pi) ** 20
        return -jnp.where(valid, t, 0.0).sum(axis=1) + bias
    raise ValueError(fn)


def _row_index(pop_block: int) -> jax.Array:
    """(pop_block,) absolute row index of this grid step (TPU needs >=2D iota)."""
    base = pl.program_id(0) * pop_block
    return base + jax.lax.broadcasted_iota(jnp.int32, (pop_block, 1), 0)[:, 0]


def _kernel(x_ref, shift_ref, o_ref, *, fn: str, dim: int, bias: float,
            n_rows: int):
    x = x_ref[...].astype(jnp.float32) - shift_ref[...].astype(jnp.float32)
    fit = _eval_tile(x, fn, dim, bias)
    # Pad rows from the pop_block round-up carry +inf fitness so they can
    # never be selected downstream (satellite: no clamp-overlap reliance).
    row_ok = _row_index(x.shape[0]) < n_rows
    o_ref[...] = jnp.where(row_ok, fit, jnp.inf)[:, None].astype(o_ref.dtype)


def bench_eval(pop: jax.Array, fn: str, shift: jax.Array | None = None,
               bias: float = 0.0, pop_block: int | None = None, *,
               interpret: bool | None = None,
               kernel_cfg: KernelConfig | None = None) -> jax.Array:
    """pop: (P, D) f32 -> fitness (P,). ``shift``: (D,) offset (CEC'2008).

    Tiling comes from ``kernel_cfg`` (a :class:`KernelConfig`, typically
    threaded from ``ExecutorConfig.kernel``); unset fields are filled by the
    ``kernels.autotune`` roofline model for this shape-class. Explicit
    ``pop_block``/``interpret`` keywords override the config.
    """
    if fn not in EVAL_TAGS:
        raise ValueError(
            f"no kernel body for eval tag {fn!r}; implemented: {EVAL_TAGS} "
            f"(kernels.registry maps function names to these tags)")
    P, D = pop.shape
    cfg = autotune.resolve(
        autotune.merge(kernel_cfg, pop_block=pop_block, interpret=interpret),
        "bench_eval", P, D, tag=fn)
    dt = jnp.dtype(cfg.dtype)
    Dp = max(cfg.dim_pad, (D + 127) // 128 * 128)
    Pp = (P + cfg.pop_block - 1) // cfg.pop_block * cfg.pop_block
    x = jnp.pad(pop, ((0, Pp - P), (0, Dp - D))).astype(dt)
    s = jnp.zeros((Dp,), dt) if shift is None else \
        jnp.pad(shift, (0, Dp - D)).astype(dt)
    kernel = functools.partial(_kernel, fn=fn, dim=D, bias=bias, n_rows=P)
    out = pl.pallas_call(
        kernel,
        grid=(Pp // cfg.pop_block,),
        in_specs=[
            pl.BlockSpec((cfg.pop_block, Dp), lambda i: (i, 0)),
            pl.BlockSpec((1, Dp), lambda i: (0, 0)),
        ],
        # A (Pp, 1) column, like the other kernels' fitness outputs: Mosaic
        # refuses a rank-1 block that is neither 128-aligned nor the whole array.
        out_specs=pl.BlockSpec((cfg.pop_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        interpret=cfg.interpret,
    )(x, s[None, :])
    return out[:P, 0]
