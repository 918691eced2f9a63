"""The work a generation requires, counted from the algorithm, not from what
one implementation compiles to, so that a change to the program cannot move
the denominator of a roofline share.

Bytes: every island reads and writes its ``(P, D)`` float32 population once
per generation, and reads and writes its ``(P,)`` fitness. This assumes what
the engine does today, that state crosses HBM between generations; an engine
that keeps a population on chip across generations needs a recount.

Operations: ``P * D * C_FN[fn]``, the floating-point operations of one
objective evaluation per coordinate (an add, multiply, compare or
transcendental each counts one).
"""
from __future__ import annotations

F32_BYTES = 4

# operations per coordinate of one evaluation
C_FN = {
    # z = x - o + 1 (2); z1 - z0^2 (2); 100 (.)^2 (2); (1 - z0)^2 (2); add, sum (2)
    "shifted_rosenbrock": 10,
    "sphere": 2,                 # square, sum
    "rastrigin": 6,              # x^2, 2 pi x, cos, 10 cos, subtract, sum
    "ackley": 6,                 # x^2, sum, 2 pi x, cos, sum; the tail is per point
    "griewank": 7,               # x^2, sum, x / sqrt(i), cos, product
}


def generation(fn: str, pop: int, dim: int, islands: int = 1) -> tuple[float, float]:
    """(bytes, operations) one generation of ``islands`` islands requires."""
    rows = pop * islands
    nbytes = 2 * rows * dim * F32_BYTES + 2 * rows * F32_BYTES
    return float(nbytes), float(rows * dim * C_FN[fn])


def least_time(fn: str, pop: int, dim: int, islands: int, peak: dict) -> float:
    """Seconds one generation takes at the chip's peak: the larger of its bytes
    over HBM bandwidth and its operations over the peak rate."""
    nbytes, ops = generation(fn, pop, dim, islands)
    return max(nbytes / peak["hbm_bytes_per_s"], ops / peak["flops"])
