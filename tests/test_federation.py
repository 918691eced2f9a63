"""Cross-host federation fault-injection suite (DESIGN.md §13, ISSUE 8).

Spawns real ``opt_serve`` subprocesses over TCP-JSONL and drives them through
``launch/federate.py``. The headline contract: SIGKILL a worker mid-run and
the coordinator revives it from its checkpoint store (``--resume-dir``, PR 7
manifests) — and because every job seed and warm-routing hop is a pure
function of :class:`FederationConfig`, the finished federation's incumbent is
**identical** to an uninterrupted fixed-seed run.

Marked ``slow`` (multi-second subprocess harness); CI's federation-smoke job
runs it explicitly.
"""
import time

import numpy as np
import pytest

from repro.launch import federate as fed
from repro.launch.federate import (FederationConfig, FederationCoordinator,
                                   WorkerSpec, federate)

pytestmark = pytest.mark.slow


def _cfg(tmp_path, name, **kw):
    base = dict(fn="rastrigin", dim=4, legs=2, evals_per_leg=1200,
                seed=5, pop=16, n_islands=2, sync_every=5,
                checkpoint_root=str(tmp_path / name),
                workers=(WorkerSpec(), WorkerSpec()))
    base.update(kw)
    return FederationConfig(**base)


def test_federation_two_workers_runs_and_routes(tmp_path):
    res = federate(_cfg(tmp_path, "plain"))
    assert res.revived == 0 and res.resubmitted == 0
    assert len(res.legs) == 2 and len(res.legs[0]) == 2
    assert np.isfinite(res.value) and len(res.arg) == 4
    # leg results are real per-worker jobs with distinct seeds
    vals0 = [r["value"] for r in res.legs[0]]
    assert len(set(vals0)) == 2


def test_federation_is_deterministic(tmp_path):
    r1 = federate(_cfg(tmp_path, "d1"))
    r2 = federate(_cfg(tmp_path, "d2"))
    assert r1.value == r2.value and r1.arg == r2.arg


def test_federation_heterogeneous_workers(tmp_path):
    cfg = _cfg(tmp_path, "het",
               workers=(WorkerSpec(algo="de"), WorkerSpec(algo="pso")))
    res = federate(cfg)
    assert np.isfinite(res.value) and len(res.legs) == 2


def test_federation_survives_sigkilled_worker(tmp_path):
    # uninterrupted reference
    ref = federate(_cfg(tmp_path, "ref"))
    # same federation, SIGKILL worker 1 after leg 0's submits land — it is
    # revived with --resume-dir and the run must converge to the same answer
    cfg = _cfg(tmp_path, "kill")
    coord = FederationCoordinator(cfg)

    def fault(leg):
        if leg == 0:
            time.sleep(0.3)          # let the bucket start and checkpoint
            coord.workers[1].kill()

    coord.fault_hook = fault
    coord.start()
    try:
        res = coord.run()
    finally:
        coord.close()
    assert res.revived >= 1
    assert res.value == ref.value
    assert res.arg == ref.arg
    assert [[r["value"] for r in leg] for leg in res.legs] == \
           [[r["value"] for r in leg] for leg in ref.legs]


def test_federation_refuses_workers_sharing_a_chip(tmp_path, monkeypatch):
    """Two workers that would both load the TPU on a one-chip host are
    refused before any process starts; CPU workers and a lone chip worker
    pass the guard."""
    monkeypatch.setattr(fed, "host_chip_count", lambda: 1)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    coord = FederationCoordinator(_cfg(tmp_path, "chips"))
    with pytest.raises(ValueError, match=r"2 opt_serve workers .* 1 TPU chip"):
        coord.start()
    assert all(w.proc is None for w in coord.workers)
    fed.check_chip_workers(2, {"JAX_PLATFORMS": "cpu"})
    fed.check_chip_workers(1, {})
    monkeypatch.setattr(fed, "host_chip_count", lambda: 0)
    fed.check_chip_workers(2, {})                  # no chip: nothing to share
