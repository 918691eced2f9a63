"""Least time of one generation over the device time one generation took, on
a cell of chunked DE.

Least time: the larger of the bytes and the operations a generation requires
(``bench/work.py``) over the chip's HBM bandwidth and peak rate
(``bench/peaks.py``), for the islands one chip holds. Device time per
generation: busy time in the trace over the generations run in the traced
window, counted from the trace itself. Chunked DE runs its chunks in a loop
that carries the population, once per generation and inside the loops over
generations and rounds, which carry it too; so the generations are the runs
of the population-carrying ``while`` that ran most often (``tracing.reduce``
counts a run cut by an edge of the window by its share inside). Any other
policy finds nothing to read here.
"""
from bench import work


def generations(rec: dict) -> float | None:
    """Generations in the traced window, from its loop runs."""
    s = rec["solve"]
    shape = f"f32[{s['pop']},{s['dim']}]"
    runs = [r for _, r, text in rec["trace"].get("loops", []) if shape in text]
    return max(runs) if runs else None


def read(rec: dict):
    t, s = rec.get("trace"), rec.get("solve")
    if (rec.get("driver") != "solve" or not t or not s or not s.get("chunked")
            or not rec.get("peak") or t["busy_s"] <= 0):
        return None
    gens = generations(rec)
    if not gens:
        return None
    per_chip = s["islands"] / max(1, t["devices"])
    least = work.least_time(s["fn"], s["pop"], s["dim"], per_chip, rec["peak"])
    return 100.0 * least * gens / t["busy_s"]
