"""Least time of the generations run in the traced window over the device
time they took, on a cell that solves problems through the service.

Least time: the larger of the bytes and the operations one generation of the
islands one chip holds requires (``bench/work.py``) over the chip's HBM
bandwidth and peak rate (``bench/peaks.py``), times the generations. Those are
the runs of the scan over a round's generations (the ``while`` directly under
``popt.round``) times ``sync_every``, mean over the chips, a run cut by an
edge of the window counted by its share inside, as ``bench/phases.py`` counts
them. Device time: busy time in the window, mean over the chips.
"""
from bench import work


def read(rec: dict):
    t, s = rec.get("trace"), rec.get("served_solve")
    if (rec.get("driver") != "served_solve" or not t or not s
            or not t.get("generations") or not rec.get("peak")
            or t["busy_s"] <= 0):
        return None
    per_chip = s["islands"] / max(1, t["devices"])
    least = work.least_time(s["fn"], s["pop"], s["dim"], per_chip, rec["peak"])
    return 100.0 * least * t["generations"] / t["busy_s"]
