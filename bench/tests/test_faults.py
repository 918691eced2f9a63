"""Whole runs on the CPU, past the harness's look for a chip, at sizes a test
run holds: a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct, once for each fault a cell can have."""
import json
import os

import jax.numpy as jnp
import pytest

from bench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = [("table1.de_chunked", "tiny_table1", "back_to_back")]


def tiny_bench() -> dict:
    """``BENCHMARK.json``'s metrics over its cells, each on a small copy of
    its configuration in ``tests/data``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    b["configs"], b["workloads"] = [], []
    for name, cfg, mix in CELLS:
        b["configs"].append({"name": cfg, "source": "test", "reduced": [],
                             "file": f"bench/tests/data/{cfg}.json", "why": "test"})
        b["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                               "chips": 1, "why": "test"})
    return b


def run(workload: str, seed: int = 3, seconds: float = 1.0) -> dict:
    """One whole run of a cell of :func:`tiny_bench` on the CPU."""
    return harness.run(ROOT, workload, seed, seconds, False,
                       require_chip=False, bench=tiny_bench())


# -- faults planted in the program -------------------------------------------

def state_unchanged(mp):
    from repro.core.islands import IslandOptimizer
    mp.setattr(IslandOptimizer, "_round_fn",
               lambda self, algo: (lambda state, key: state))


def half_batch_left_out(mp):
    from repro.core import executor
    orig = executor._make_eval_once

    def half(f, cfg):
        once = orig(f, cfg)

        def ev(pop):
            fit = once(pop)
            return fit.at[pop.shape[0] // 2:].set(jnp.inf)
        return ev
    mp.setattr(executor, "_make_eval_once", half)


def answer_altered(mp):
    from repro.core.islands import IslandOptimizer
    from repro.core.scheduler import ShapeBucketScheduler
    orig_min, orig_fin = IslandOptimizer.minimize, ShapeBucketScheduler._finalize

    def minimize(self, f, key, warm=None):
        r = orig_min(self, f, key, warm)
        r.value = r.value * (1 + 1e-3)
        return r

    def finalize(self, job, status, result=None, error=None):
        if result is not None:
            result.value = result.value * (1 + 1e-3)
        return orig_fin(self, job, status, result, error)
    mp.setattr(IslandOptimizer, "minimize", minimize)
    mp.setattr(ShapeBucketScheduler, "_finalize", finalize)


FAULTS = {
    "table1.de_chunked": [state_unchanged, half_batch_left_out, answer_altered],
}
CASES = [(w, f) for w, fs in FAULTS.items() for f in [None] + fs]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__ if f else 'sound'}" for w, f in CASES])
def test_fault_turns_correct_false(workload, fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    check_fault(workload, fault, 1.0)


def check_fault(workload, fault, seconds):
    r = run(workload, seconds=seconds)
    assert r["attempted"] > 0
    if fault is None:
        assert r["correct"], r["checks"]
    else:
        assert not r["correct"], r["checks"]
