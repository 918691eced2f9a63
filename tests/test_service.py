"""Service-hardening tests (DESIGN.md §12): streaming progress, cooperative
cancellation, priority lanes, backpressure load-shed, checkpoint/resume, and
the fault-injection + soak layer that proves them.

Three fault surfaces are exercised:
  * in-process "kill" via the scheduler's ``fault_hook`` raising
    :class:`AbandonRun` — a worker walks away mid-run leaving checkpoints
    and job records exactly as a SIGKILL would;
  * a real SIGKILL of the TCP server subprocess, restarted with
    ``--resume-dir`` (the paper's network-of-JVMs restart story);
  * a checkpoint with a corrupted checksum, which must be rejected cleanly.

The resume contract is *bit-identity*: a killed-and-resumed fixed-seed run
must produce the same incumbent (value, argument, eval/gen accounting and
per-round history) as an uninterrupted run.

Only the Hypothesis property test is gated on the dev-only ``hypothesis``
dependency (the ``tests/test_optim.py`` convention)."""
import json
import os
import pathlib
import random
import signal
import socket
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (AbandonRun, OptRequest, SchedulerOverloaded,
                        ShapeBucketScheduler, UnknownJob)
from repro.launch.opt_serve import OptimizationService

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:       # dev-only dep; pip install -r requirements-dev.txt
    given = None


def _req(seed=0, **kw):
    base = dict(fn="sphere", algo="de", dim=4, pop=16, n_islands=2,
                sync_every=5, max_evals=1500, migration="ring")
    base.update(kw)
    return OptRequest(seed=seed, **base)


def _long_req(seed=3, **kw):
    """Many cheap sync rounds — plenty of boundaries to stream/cancel/
    checkpoint at. 2 islands * pop 16 * sync_every 1 = 32 evals/round."""
    base = dict(fn="rastrigin", algo="de", dim=6, pop=16, n_islands=2,
                sync_every=1, max_evals=32 + 32 * 120, migration="ring")
    base.update(kw)
    return OptRequest(seed=seed, **base)


def _uninterrupted(req: OptRequest):
    """Reference result: the same request through a fresh blocking scheduler."""
    sched = ShapeBucketScheduler()
    jid = sched.submit(req)
    return sched.result(jid).result


# --- streaming progress ------------------------------------------------------

def test_poll_streams_round_progress_while_running():
    """With a worker pool, pollers see round/best_val/evals advance while the
    bucket is still running — the submit/poll/result loop is no longer blind
    between submit and done."""
    sched = ShapeBucketScheduler(workers=1)
    jid = sched.submit(_long_req())
    sched.flush()
    seen = []
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        r = sched.poll(jid)
        if r.status == "done":
            break
        if r.status == "running" and r.round is not None:
            seen.append((r.round, r.best_val, r.evals_done, r.n_rounds))
        time.sleep(0.002)
    resp = sched.result(jid)
    assert resp.status == "done"
    assert seen, "never observed streamed progress while running"
    rounds = [s[0] for s in seen]
    assert rounds == sorted(rounds)                  # round counter advances
    assert all(s[3] == seen[0][3] for s in seen)     # n_rounds is stable
    assert all(0 < s[0] <= s[3] for s in seen)
    assert all(s[1] is not None and s[2] > 0 for s in seen)
    # incumbent never worsens round-over-round (DE keeps the best)
    vals = [s[1] for s in seen]
    assert all(b <= a + 1e-12 for a, b in zip(vals, vals[1:]))
    # final record carries the full-budget accounting
    assert resp.result.n_evals == _uninterrupted(_long_req()).n_evals
    sched.close()


def test_stepped_run_bit_identical_to_blocking_reference():
    """The pool's host-stepped bucket runner replays minimize_many's exact
    trajectory: value, argument and per-round history all match."""
    req = _long_req(seed=11)
    ref = _uninterrupted(req)
    sched = ShapeBucketScheduler(workers=1)
    jid = sched.submit(req)
    sched.flush()
    got = sched.result(jid).result
    assert got.value == ref.value
    assert np.array_equal(np.asarray(got.arg), np.asarray(ref.arg))
    assert np.array_equal(np.asarray(got.history), np.asarray(ref.history))
    assert got.n_evals == ref.n_evals and got.n_gens == ref.n_gens
    sched.close()


# --- cancellation ------------------------------------------------------------

def test_cancel_running_job_returns_partial_result():
    sched = ShapeBucketScheduler(workers=1)
    jid = sched.submit(_long_req())
    sched.flush()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:      # wait for a round boundary so the
        r = sched.poll(jid)                 # run is provably preemptible
        if r.status == "running" and (r.round or 0) >= 1:
            break
        assert r.status != "done", "job finished before it could be cancelled"
        time.sleep(0.002)
    reply = sched.cancel(jid)
    assert reply["status"] in ("cancelling", "cancelled")
    resp = sched.result(jid)
    assert resp.status == "cancelled"
    assert resp.result is not None                    # partial incumbent
    assert 0 < resp.result.n_gens < _uninterrupted(_long_req()).n_gens
    assert resp.result.n_evals < _long_req().max_evals
    assert len(resp.result.history) == resp.round
    sched.close()


def test_cancel_queued_job_withdraws_it():
    sched = ShapeBucketScheduler(workers=1)
    jid = sched.submit(_req())
    reply = sched.cancel(jid)
    assert reply == {"id": jid, "status": "cancelled"}
    assert sched.poll(jid).status == "cancelled"
    assert sched.poll(jid).result is None             # never ran
    assert sched.pending_buckets() == []              # bucket emptied


def test_cancel_unknown_and_finished_ids_are_structured():
    svc = OptimizationService()
    assert svc.handle({"op": "cancel", "id": "ghost"}) == {
        "error": "unknown-id", "id": "ghost"}
    r = svc.handle({"op": "submit", "request":
                    {"fn": "sphere", "dim": 3, "pop": 8, "max_evals": 400}})
    svc.handle({"op": "flush"})
    reply = svc.handle({"op": "cancel", "id": r["id"]})
    assert reply["error"] == "already-finished" and reply["status"] == "done"
    with pytest.raises(UnknownJob):
        svc.scheduler.cancel("ghost")


# --- priority lanes + backpressure ------------------------------------------

def test_priority_lane_orders_bucket_execution():
    """While the single worker is pinned on a blocker bucket, a high-priority
    bucket enqueued AFTER a low-priority one must run first."""
    started, release, order = threading.Event(), threading.Event(), []

    def hook(key, r):
        order.append(key)
        if key == blocker_key and r == 1:
            started.set()
            release.wait(120)

    sched = ShapeBucketScheduler(workers=1, fault_hook=hook)
    blocker = _long_req(seed=0)
    blocker_key = blocker.shape_class()
    sched.submit(blocker)
    sched.flush()
    assert started.wait(120)                       # worker now provably pinned
    lo = sched.submit(_req(seed=1, dim=5), priority=0)
    hi = sched.submit(_req(seed=1, dim=6), priority=9)
    sched.flush()                                  # both land on the heap
    release.set()
    assert sched.result(lo).status == "done"
    assert sched.result(hi).status == "done"
    keys = [k for k in order
            if k in (_req(dim=5).shape_class(), _req(dim=6).shape_class())]
    assert keys, "neither prioritized bucket ever ran"
    assert keys[0] == _req(dim=6).shape_class()    # high priority went first
    sched.close()


def test_backpressure_sheds_load_with_retry_after():
    started, release = threading.Event(), threading.Event()

    def hook(key, r):
        started.set()
        release.wait(120)

    sched = ShapeBucketScheduler(workers=1, max_pending=2, fault_hook=hook)
    svc = OptimizationService(scheduler=sched)
    blocker = sched.submit(_long_req())
    sched.flush()
    assert started.wait(120)                       # worker pinned on round 1
    sched.submit(_req(seed=1))
    sched.submit(_req(seed=2))
    with pytest.raises(SchedulerOverloaded) as ei:
        sched.submit(_req(seed=3))
    assert ei.value.retry_after_ms > 0
    reply = svc.handle({"op": "submit",
                        "request": {"fn": "sphere", "dim": 4, "pop": 16,
                                    "n_islands": 2, "max_evals": 1500,
                                    "sync_every": 5, "seed": 4}})
    assert reply["error"] == "overloaded" and reply["retry_after_ms"] > 0
    assert sched.stats()["shed"] == 2
    release.set()
    assert sched.drain(timeout=120)
    assert sched.result(blocker).status == "done"
    sched.close()


# --- concurrency / soak ------------------------------------------------------

def test_soak_concurrent_submit_poll_cancel_no_lost_responses():
    """N submitter threads (mixed shapes) race an aggressive poller and a
    canceller against a 2-worker pool: every job reaches a final status, a
    fetched result never reappears (fetch-once), and no reply is ever a
    traceback-shaped surprise."""
    svc = OptimizationService(workers=2, max_batch=4, flush_ms=5.0)
    shapes = [dict(fn="sphere", dim=3, pop=8, n_islands=1, max_evals=400),
              dict(fn="rastrigin", dim=4, pop=8, n_islands=2, max_evals=600,
                   sync_every=2),
              dict(fn="sphere", dim=5, pop=16, n_islands=2, max_evals=800,
                   sync_every=2)]
    results, errors = {}, []
    known_ids, mu = [], threading.Lock()
    stop = threading.Event()

    def submitter(t):
        rng = random.Random(t)
        for i in range(5):
            req = dict(shapes[(t + i) % len(shapes)], seed=rng.randrange(99))
            r = svc.handle({"op": "submit", "request": req})
            if "error" in r:
                errors.append(("submit", r))
                continue
            with mu:
                known_ids.append(r["id"])
            out = svc.handle({"op": "result", "id": r["id"]})
            with mu:
                if r["id"] in results:
                    errors.append(("double-result", r["id"]))
                results[r["id"]] = out
            # fetch-once eviction: a second result is a structured error
            again = svc.handle({"op": "result", "id": r["id"]})
            if again.get("error") != "unknown-id":
                errors.append(("no-evict", again))

    def poller():
        rng = random.Random(1234)
        while not stop.is_set():
            with mu:
                ids = list(known_ids)
            if ids:
                reply = svc.handle({"op": "poll", "id": rng.choice(ids)})
                ok = ("status" in reply) or (reply.get("error") == "unknown-id")
                if not ok:
                    errors.append(("poll", reply))
            svc.handle({"op": "status"})
            time.sleep(0.001)

    def canceller():
        req = dict(fn="rastrigin", dim=6, pop=16, n_islands=2, sync_every=1,
                   max_evals=32 + 32 * 150, seed=7)
        r = svc.handle({"op": "submit", "request": req})
        svc.handle({"op": "flush"})
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            p = svc.handle({"op": "poll", "id": r["id"]})
            if p.get("status") in ("done", "cancelled") or p.get("round"):
                break
            time.sleep(0.002)
        svc.handle({"op": "cancel", "id": r["id"]})
        out = svc.handle({"op": "result", "id": r["id"]})
        if out.get("status") not in ("cancelled", "done"):
            errors.append(("cancel", out))

    threads = ([threading.Thread(target=submitter, args=(t,)) for t in range(6)]
               + [threading.Thread(target=canceller)])
    pollt = threading.Thread(target=poller, daemon=True)
    pollt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "soak thread hung"
    stop.set()
    pollt.join(timeout=10)
    assert errors == []
    assert len(results) == 30                       # no lost responses
    assert all(r.get("status") == "done" and "value" in r
               for r in results.values())
    stats = svc.handle({"op": "stats"})
    assert stats["cancelled"] >= 0 and stats["workers"] == 2
    svc.scheduler.close()


# --- checkpoint / resume (in-process fault injection) -----------------------

def _abandon_at(round_no, key_filter=None):
    """fault_hook raising AbandonRun at a round boundary — the in-process
    SIGKILL: the worker walks away leaving checkpoints + job records."""
    fired = threading.Event()

    def hook(key, r):
        if key_filter is not None and key != key_filter:
            return
        if r == round_no:
            fired.set()
            raise AbandonRun(f"injected kill at round {r}")

    return hook, fired


def test_kill_and_resume_is_bit_identical(tmp_path):
    req = _long_req(seed=5)
    ref = _uninterrupted(req)

    hook, fired = _abandon_at(6)
    sched = ShapeBucketScheduler(workers=1, checkpoint_dir=str(tmp_path),
                                 checkpoint_every=2, fault_hook=hook)
    jid = sched.submit(req)
    sched.flush()
    assert fired.wait(timeout=120), "fault hook never fired"
    time.sleep(0.05)                       # let the worker unwind
    assert sched.poll(jid).status == "running"      # orphaned, like a SIGKILL
    run_dirs = [d for d in os.listdir(tmp_path) if d.startswith("run_")]
    assert len(run_dirs) == 1, "expected exactly one interrupted run on disk"
    sched.close()

    sched2 = ShapeBucketScheduler()        # fresh process, blocking mode
    summary = sched2.resume(str(tmp_path))
    assert summary["failed"] == []
    assert [jid] == summary["resumed"][0]["jobs"]
    assert summary["resumed"][0]["round"] == 6      # latest committed snapshot
    got = sched2.result(jid)
    assert got.status == "done"
    assert got.result.value == ref.value                        # bit-identical
    assert np.array_equal(np.asarray(got.result.arg), np.asarray(ref.arg))
    assert np.array_equal(np.asarray(got.result.history),
                          np.asarray(ref.history))
    assert got.result.n_evals == ref.n_evals
    assert got.result.n_gens == ref.n_gens
    # completed runs clean their snapshots: nothing left to double-resume
    assert [d for d in os.listdir(tmp_path) if d.startswith("run_")] == []
    assert sched2.stats()["resumed"] == 1


def test_corrupted_checkpoint_is_rejected_cleanly(tmp_path):
    hook, fired = _abandon_at(6)
    sched = ShapeBucketScheduler(workers=1, checkpoint_dir=str(tmp_path),
                                 checkpoint_every=2, fault_hook=hook)
    jid = sched.submit(_long_req(seed=5))
    sched.flush()
    assert fired.wait(timeout=120)
    time.sleep(0.05)
    sched.close()
    run_dir = next(tmp_path.glob("run_*"))
    step_dir = sorted(run_dir.glob("step_*"))[-1]
    leaf = sorted(step_dir.glob("leaf_*.npy"))[0]
    raw = bytearray(leaf.read_bytes())
    raw[-4] ^= 0xFF                        # flip payload bits: checksum breaks
    leaf.write_bytes(bytes(raw))

    sched2 = ShapeBucketScheduler()
    summary = sched2.resume(str(tmp_path))
    assert summary["resumed"] == []
    assert len(summary["failed"]) == 1
    assert "checksum" in summary["failed"][0]["error"]
    # the job comes back as a structured error, and the scheduler still works
    resp = sched2.poll(jid)
    assert resp.status == "error" and "checkpoint" in resp.error
    assert sched2.stats()["resume_failed"] == 1
    ok = sched2.submit(_req())
    assert sched2.result(ok).status == "done"


# --- SIGKILL the TCP server (subprocess harness) ----------------------------

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _start_server(extra_args, timeout=120):
    """Launch opt_serve --tcp 0 in a subprocess; returns (proc, port)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.opt_serve", "--tcp", "0",
         *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu"),
    )
    port, lines = None, []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stderr.readline()
        if not line:
            if proc.poll() is not None:
                break
            continue
        lines.append(line)
        if "listening on" in line:
            port = int(line.rsplit(":", 1)[1])
            break
    if port is None:
        proc.kill()
        raise RuntimeError(f"server never came up: {''.join(lines)}")
    return proc, port


class _Client:
    """Minimal JSONL-over-TCP client for the subprocess harness."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=300)
        self.f = self.sock.makefile("rw")

    def call(self, msg):
        self.f.write(json.dumps(msg) + "\n")
        self.f.flush()
        return json.loads(self.f.readline())

    def close(self):
        self.sock.close()


@pytest.mark.slow
def test_sigkill_tcp_server_resume_bit_identical(tmp_path):
    """The real thing: SIGKILL the serving process mid-run, restart with
    --resume-dir, and the resumed job's final incumbent is bit-identical to
    an uninterrupted fixed-seed run."""
    req = dict(fn="rastrigin", algo="de", dim=6, pop=16, n_islands=2,
               sync_every=1, max_evals=32 + 32 * 800, seed=13,
               migration="ring")
    ref = _uninterrupted(OptRequest(**req))
    ckpt = str(tmp_path / "ckpt")

    proc, port = _start_server(["--workers", "1", "--flush-ms", "10",
                                "--checkpoint-dir", ckpt,
                                "--checkpoint-every", "2"])
    try:
        cl = _Client(port)
        sub = cl.call({"op": "submit", "request": req})
        jid = sub["id"]
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            p = cl.call({"op": "poll", "id": jid})
            assert p.get("status") != "done", \
                "job finished before the kill landed; raise max_evals"
            if p.get("round", 0) >= 10:
                break
            time.sleep(0.01)
        else:
            pytest.fail("never saw enough progress to kill mid-run")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        cl.close()
    finally:
        if proc.poll() is None:
            proc.kill()

    assert any(d.startswith("run_") for d in os.listdir(ckpt)), \
        "no checkpoint survived the kill"
    proc2, port2 = _start_server(["--workers", "1", "--resume-dir", ckpt])
    try:
        cl2 = _Client(port2)
        out = cl2.call({"op": "result", "id": jid})
        assert out["status"] == "done"
        assert out["value"] == float(ref.value)                # bit-identical
        assert out["arg"] == [float(v) for v in np.asarray(ref.arg).ravel()]
        assert out["n_evals"] == ref.n_evals
        assert out["n_gens"] == ref.n_gens
        # a second fetch is evicted; stats show the resume happened
        assert cl2.call({"op": "result", "id": jid})["error"] == "unknown-id"
        assert cl2.call({"op": "stats"})["resumed"] == 1
        assert cl2.call({"op": "quit"}) == {"bye": True}
        cl2.close()
    finally:
        proc2.kill()


# --- protocol regressions (satellite fixes) ---------------------------------

def test_result_unknown_id_is_structured_not_a_keyerror():
    svc = OptimizationService()
    assert svc.handle({"op": "result", "id": "nope"}) == {
        "error": "unknown-id", "id": "nope"}
    # evicted ids degrade to the same structured error
    r = svc.handle({"op": "submit", "request":
                    {"fn": "sphere", "dim": 3, "pop": 8, "max_evals": 400}})
    assert svc.handle({"op": "result", "id": r["id"]})["status"] == "done"
    assert svc.handle({"op": "result", "id": r["id"]}) == {
        "error": "unknown-id", "id": r["id"]}


def test_status_op_lists_per_bucket_counts():
    svc = OptimizationService(max_batch=100, flush_ms=1e6)
    for seed in range(3):
        svc.handle({"op": "submit", "request":
                    {"fn": "sphere", "dim": 4, "pop": 16, "n_islands": 2,
                     "sync_every": 5, "max_evals": 1500, "seed": seed}})
    svc.handle({"op": "submit", "request":
                {"fn": "rastrigin", "dim": 5, "pop": 16, "max_evals": 900}})
    out = svc.handle({"op": "status"})
    assert len(out["buckets"]) == 2
    by_fn = {k.split("|")[0]: v for k, v in out["buckets"].items()}
    assert by_fn["sphere"] == {"counts": {"queued": 3},
                               "sync_policy": "barrier"}
    assert by_fn["rastrigin"] == {"counts": {"queued": 1},
                                  "sync_policy": "barrier"}
    assert out["queue_depth"] == 0
    svc.handle({"op": "flush"})
    out = svc.handle({"op": "status"})
    assert {k.split("|")[0]: v["counts"] for k, v in
            out["buckets"].items()} == {
        "sphere": {"done": 3}, "rastrigin": {"done": 1}}
    json.dumps(out)                                  # JSONL-serializable


def test_status_op_reports_sync_policy_and_queue_depth():
    # Satellite regression (ISSUE 8): the status op must expose each
    # bucket's engine sync policy and the worker-pool queue depth — before
    # the fix it carried only the lifecycle counts.
    svc = OptimizationService(max_batch=100, flush_ms=1e6)
    svc.handle({"op": "submit", "request":
                {"fn": "sphere", "dim": 4, "pop": 16, "n_islands": 2,
                 "sync_policy": "async", "max_staleness": 2,
                 "sync_every": 5, "max_evals": 1500, "seed": 0}})
    svc.handle({"op": "submit", "request":
                {"fn": "sphere", "dim": 4, "pop": 16, "max_evals": 900}})
    out = svc.handle({"op": "status"})
    assert "queue_depth" in out and out["queue_depth"] == 0
    policies = sorted(v["sync_policy"] for v in out["buckets"].values())
    assert policies == ["async", "barrier"]
    # async vs barrier never share a bucket: sync_policy is shape-class
    assert len(out["buckets"]) == 2
    json.dumps(out)


# --- shape-class properties (hypothesis, test_optim.py conventions) ---------

_FIELD_VALUES = {
    "fn": ["sphere", "rastrigin", "rosenbrock"],
    "algo": ["de", "pso", "ga"],
    "dim": [2, 4, 8, 16],
    "max_evals": [500, 2000, 10_000],
    "pop": [8, 16, 64],
    "n_islands": [1, 2, 4],
    "sync_every": [1, 5, 10],
    "migration": ["ring", "starvation", "none"],
    "n_migrants": [0, 1, 2],
    "share_incumbent": [False, True],
    "backend": ["xla", "pallas"],
    "devices": [1, 2],
    "polish": ["none", "asd", "fcg"],
    "polish_every": [1, 2],
    "polish_topk": [2, 4],
    "polish_steps": [1, 3],
    "params": [{}, {"F": 0.6}, {"F": 0.6, "CR": 0.8}],
    "sync_policy": ["barrier", "async"],
    "max_staleness": [0, 2],
    "warm": [[], [[0.1, 0.2]], [[0.1, 0.2], [0.3, 0.4]]],
}

if given is not None:
    _fields = st.fixed_dictionaries({
        k: st.sampled_from(v) for k, v in _FIELD_VALUES.items()})

    @settings(max_examples=40, deadline=None)
    @given(_fields, st.integers(0, 2**31 - 1), st.randoms())
    def test_shape_class_stable_under_field_reordering(d, seed, rng):
        items = list(dict(d, seed=seed).items())
        rng.shuffle(items)
        a = OptRequest.from_dict(dict(d, seed=seed))
        b = OptRequest.from_dict(dict(items))
        assert a.shape_class() == b.shape_class()
        hash(a.shape_class())                        # stays a valid dict key

    @settings(max_examples=40, deadline=None)
    @given(_fields, st.integers(0, 2**31 - 1), st.integers(0, 2**31 - 1),
           st.data())
    def test_seed_shares_bucket_any_other_field_never_does(d, s1, s2, data):
        base = OptRequest.from_dict(dict(d, seed=s1))
        assert base.shape_class() == OptRequest.from_dict(
            dict(d, seed=s2)).shape_class()          # seed never splits
        field = data.draw(st.sampled_from(sorted(_FIELD_VALUES)))
        alt = data.draw(st.sampled_from(
            [v for v in _FIELD_VALUES[field] if v != d[field]]))
        changed = OptRequest.from_dict(dict(d, seed=s1, **{field: alt}))
        assert base.shape_class() != changed.shape_class()
else:
    @pytest.mark.skip(reason="hypothesis not installed; "
                             "pip install -r requirements-dev.txt")
    def test_shape_class_stable_under_field_reordering():
        pass

    @pytest.mark.skip(reason="hypothesis not installed; "
                             "pip install -r requirements-dev.txt")
    def test_seed_shares_bucket_any_other_field_never_does():
        pass


def test_portfolio_normalizes_unused_algo_out_of_the_key():
    """The one documented exception: in portfolio mode ``algo`` is ignored by
    the engine, so it is normalized out of the bucket key."""
    a = OptRequest.from_dict({"fn": "sphere", "n_islands": 4,
                              "portfolio": ["de", "pso"], "algo": "de"})
    b = OptRequest.from_dict({"fn": "sphere", "n_islands": 4,
                              "portfolio": ["de", "pso"], "algo": "ga"})
    assert a.shape_class() == b.shape_class()
    c = OptRequest.from_dict({"fn": "sphere", "n_islands": 4,
                              "portfolio": ["de", "sa"]})
    assert a.shape_class() != c.shape_class()


def test_compile_cache_dir_is_the_env_or_a_fixed_checkout_path(monkeypatch):
    """``opt_serve``'s compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it; nothing is overridden), else ``.jax_cache`` at the root of
    the checkout, the same path on every call; off on the CPU backend. The
    config writes are intercepted, so this test turns no cache on."""
    from pathlib import Path
    from repro.launch import compile_cache as cc
    updates = []
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(cc.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(cc.CACHE_ENV, "/cache/from/env")
    assert cc.enable_compile_cache() == "/cache/from/env" and updates == []
    monkeypatch.delenv(cc.CACHE_ENV)
    repo = Path(__file__).resolve().parents[1]
    path = cc.enable_compile_cache()
    assert path == str(repo / ".jax_cache") == cc.enable_compile_cache()
    assert updates == [("jax_compilation_cache_dir", path)] * 2
    updates.clear()
    monkeypatch.setattr(cc.jax, "default_backend", lambda: "cpu")
    assert cc.enable_compile_cache() is None and updates == []
