"""Share of the traced window in which no op ran on the device, on a cell that
solves problems back to back through the service (mean over the chips)."""


def read(rec: dict):
    t = rec.get("trace")
    if rec.get("driver") != "served_solve" or not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
