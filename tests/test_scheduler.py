"""Multi-job service tests: shape-bucketing, bit-identical parity with
sequential ``IslandOptimizer.minimize``, budget accounting, batching policy
and the JSONL protocol (DESIGN.md §5)."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import (ALGORITHMS, ExecutorConfig, IslandConfig,
                        IslandOptimizer, OptRequest, ShapeBucketScheduler,
                        make_batch_evaluator)
from repro.functions import get
from repro.launch.opt_serve import OptimizationService


def _req(seed=0, **kw):
    base = dict(fn="sphere", algo="de", dim=4, pop=16, n_islands=2,
                sync_every=5, max_evals=1500, migration="ring")
    base.update(kw)
    return OptRequest(seed=seed, **base)


def _sequential(req: OptRequest):
    cfg = IslandConfig(n_islands=req.n_islands, pop=req.pop, dim=req.dim,
                       sync_every=req.sync_every, migration=req.migration,
                       n_migrants=req.n_migrants,
                       share_incumbent=req.share_incumbent,
                       max_evals=req.max_evals)
    opt = IslandOptimizer(ALGORITHMS[req.algo], cfg, params=dict(req.params))
    return opt.minimize(get(req.fn, req.dim), jax.random.PRNGKey(req.seed))


# --- request / bucket-key semantics -----------------------------------------

def test_shape_class_ignores_only_seed():
    assert _req(seed=0).shape_class() == _req(seed=7).shape_class()
    assert _req().shape_class() != _req(dim=5).shape_class()
    assert _req().shape_class() != _req(algo="pso").shape_class()
    assert _req().shape_class() != _req(backend="pallas").shape_class()
    assert _req().shape_class() != _req(params=(("w", 0.9),)).shape_class()


def test_from_dict_normalizes_params_and_rejects_unknown():
    r = OptRequest.from_dict({"fn": "sphere", "params": {"w": 0.7, "px": 0.1}})
    assert r.params == (("px", 0.1), ("w", 0.7))
    # JSON round-trips tuples as lists; the key must stay hashable
    r2 = OptRequest.from_dict({"fn": "sphere", "params": [["w", 0.7]]})
    assert r2.params == (("w", 0.7),)
    hash(r2.shape_class())
    with pytest.raises(ValueError, match="unknown"):
        OptRequest.from_dict({"fn": "sphere", "bogus": 1})


# --- scheduler correctness ---------------------------------------------------

def test_scheduler_bit_identical_to_sequential():
    """K same-shaped requests through the service == K minimize calls."""
    reqs = [_req(seed=s) for s in (0, 3, 0)]
    seq = [_sequential(r) for r in reqs]

    sched = ShapeBucketScheduler()
    ids = [sched.submit(r) for r in reqs]
    sched.flush()
    for jid, expect in zip(ids, seq):
        got = sched.result(jid)
        assert got.status == "done"
        assert got.result.value == expect.value          # bit-identical
        assert got.result.n_evals == expect.n_evals
        assert got.result.n_gens == expect.n_gens
        assert bool(jnp.all(got.result.arg == expect.arg))
    assert sched.n_dispatches == 1                       # one packed run


def test_scheduler_n_evals_budget_accounting():
    """Total evals consumed under the scheduler == same totals sequentially,
    and within each request's budget."""
    reqs = [_req(seed=s, max_evals=2000) for s in range(4)]
    sched = ShapeBucketScheduler()
    ids = [sched.submit(r) for r in reqs]
    sched.flush()
    got = [sched.result(i).result for i in ids]
    seq_total = sum(_sequential(r).n_evals for r in reqs)
    assert sum(r.n_evals for r in got) == seq_total
    assert all(r.n_evals <= 2000 for r in got)


def test_mixed_buckets_route_and_complete():
    reqs = [_req(seed=0), _req(seed=1),                  # bucket A (x2)
            _req(seed=0, dim=6),                         # bucket B
            _req(seed=0, algo="pso", params=())]         # bucket C
    sched = ShapeBucketScheduler()
    ids = [sched.submit(r) for r in reqs]
    assert len(sched.pending_buckets()) == 3
    assert sched.flush() == 4
    assert sched.n_dispatches == 3
    for jid in ids:
        assert sched.result(jid).status == "done"


def test_auto_ids_skip_client_claimed_names():
    sched = ShapeBucketScheduler()
    sched.submit(_req(seed=0), job_id="job0")            # client claims job0
    auto = sched.submit(_req(seed=1))                    # must not collide
    assert auto != "job0"
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(_req(seed=2), job_id="job0")


def test_optimizer_cache_is_lru_capped():
    sched = ShapeBucketScheduler(max_cached_buckets=2)
    for d in (3, 4, 5):
        sched._optimizer(_req(dim=d))
    assert len(sched._optimizers) == 2
    # dim=3 was evicted, dim=5 (MRU) survived
    assert _req(dim=5).shape_class() in sched._optimizers
    assert _req(dim=3).shape_class() not in sched._optimizers


def test_handle_line_rejects_non_object_json():
    from repro.launch.opt_serve import _handle_line
    svc = OptimizationService()
    for payload in ("42", "[1, 2]", '"x"'):
        reply, quit_ = _handle_line(svc, payload)
        assert "error" in reply and not quit_


def test_result_forces_flush_and_poll_does_not():
    sched = ShapeBucketScheduler()
    jid = sched.submit(_req())
    assert sched.poll(jid).status == "queued"
    resp = sched.result(jid)
    assert resp.status == "done" and resp.result is not None


def test_bad_request_errors_are_isolated_per_bucket():
    sched = ShapeBucketScheduler()
    bad = sched.submit(_req(fn="no_such_function"))
    ok = sched.submit(_req())
    sched.flush()
    assert sched.poll(bad).status == "error"
    assert "KeyError" in sched.poll(bad).error
    assert sched.poll(ok).status == "done"


def test_evaluator_cache_returns_same_callable():
    f = get("sphere")
    cfg = ExecutorConfig(backend="xla")
    assert make_batch_evaluator(f, cfg) is make_batch_evaluator(f, cfg)
    assert make_batch_evaluator(f, cfg) is not make_batch_evaluator(
        f, ExecutorConfig(backend="xla", retry_bad=False))


# --- service layer (queue + deadline flush + protocol) ----------------------

def test_service_max_batch_triggers_dispatch():
    svc = OptimizationService(max_batch=2, flush_ms=1e6)  # deadline disabled
    r1 = svc.handle({"op": "submit", "request":
                     {"fn": "sphere", "dim": 4, "pop": 16, "n_islands": 2,
                      "max_evals": 1500, "seed": 0}})
    assert r1["status"] == "queued"
    r2 = svc.handle({"op": "submit", "request":
                     {"fn": "sphere", "dim": 4, "pop": 16, "n_islands": 2,
                      "max_evals": 1500, "seed": 1}})
    assert r2["status"] == "done"                        # size-based flush
    assert svc.handle({"op": "poll", "id": r1["id"]})["status"] == "done"


def test_service_deadline_flush_via_tick():
    svc = OptimizationService(max_batch=100, flush_ms=0.0)
    r = svc.handle({"op": "submit", "request":
                    {"fn": "sphere", "dim": 4, "pop": 16, "max_evals": 1000}})
    assert svc.handle({"op": "poll", "id": r["id"]})["status"] == "queued"
    assert svc.next_deadline() is not None
    assert svc.tick() == 1                               # deadline passed
    assert svc.handle({"op": "poll", "id": r["id"]})["status"] == "done"
    assert svc.next_deadline() is None


def test_service_protocol_result_and_errors():
    svc = OptimizationService()
    r = svc.handle({"op": "submit", "request":
                    {"fn": "sphere", "dim": 3, "pop": 16, "max_evals": 1000,
                     "seed": 5}})
    out = svc.handle({"op": "result", "id": r["id"]})
    assert out["status"] == "done"
    assert len(out["arg"]) == 3 and out["n_evals"] <= 1000
    json.dumps(out)                                      # JSONL-serializable
    # fetch-once semantics: the record is evicted, the job table stays bounded
    assert "error" in svc.handle({"op": "poll", "id": r["id"]})
    assert len(svc.scheduler._jobs) == 0
    assert "error" in svc.handle({"op": "nope"})
    assert "error" in svc.handle({"op": "submit", "request": {"fn": "sphere",
                                                              "bogus": 1}})
    stats = svc.handle({"op": "stats"})
    assert stats["jobs_run"] == 1 and stats["dispatches"] == 1


def test_stdin_loop_drains_ops_arriving_in_one_write():
    """Ops written in a single chunk must all be answered while the pipe
    stays OPEN (regression: buffered readline stranded trailing ops behind a
    quiet select until EOF)."""
    import os
    import pathlib
    import subprocess
    import sys as _sys
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.Popen(
        [_sys.executable, "-m", "repro.launch.opt_serve"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, PYTHONPATH=src),
    )
    try:
        proc.stdin.write('{"op": "stats"}\n{"op": "stats"}\n{"op": "quit"}\n')
        proc.stdin.flush()                # pipe stays open: no EOF wake-up
        proc.wait(timeout=120)            # quit must terminate the loop
    except subprocess.TimeoutExpired:
        proc.kill()
        pytest.fail("serve_stdin stalled on ops delivered in one write")
    replies = [json.loads(l) for l in proc.stdout.read().splitlines() if l]
    assert len(replies) == 3
    assert replies[-1] == {"bye": True}
