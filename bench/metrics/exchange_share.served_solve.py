"""Share of the device's busy time spent on the islands' exchange: the leaf
ops under ``popt.migrate`` (the ring: each island's best, the cross-chip
``ppermute`` and the adoption) and under ``popt.sync`` (the cross-chip merge
of the incumbent), over the union of all leaf ops, both mean over the chips
and inside the traced window. A program without one of the scopes counts
what it has."""

SCOPES = ("popt.migrate", "popt.sync")


def read(rec: dict):
    t = rec.get("trace")
    if (rec.get("driver") != "served_solve" or not t or "scopes" not in t
            or t["busy_s"] <= 0):
        return None
    return 100.0 * sum(t["scopes"].get(s, 0.0) for s in SCOPES) / t["busy_s"]
