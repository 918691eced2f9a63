"""The ``served_solve`` driver and its readers: whole runs of the four-chip
cell at a small size on four CPU devices, sound and with the ring exchange
left out; the capture that puts device time down to the program's scopes;
each reader on a synthetic record."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, peaks, work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "islands8.ring.4chip"

# One child process sees four CPU devices (this one sees one). The reference
# replay is the same for every run, so the runs share one compiled replay.
RUNS = """
import json
from bench import check, harness

with open("BENCHMARK.json") as fh:
    b = json.load(fh)
b["configs"] = [{"name": "tiny_islands8_ring", "source": "test", "reduced": [],
                 "file": "bench/tests/data/tiny_islands8_ring.json",
                 "why": "test"}]
b["workloads"] = [{"name": "islands8.ring.4chip", "config": "tiny_islands8_ring",
                   "traffic": "back_to_back", "chips": 4, "why": "test"}]
shared = check.Replays()
check.Replays = lambda: shared
out = {}
for fault in ("sound", "ring_left_out"):
    if fault == "ring_left_out":
        from repro.core import migration
        migration.ring = lambda pop, fit, k=2, axis=None, n_shards=1: (pop, fit)
    r = harness.run(".", "islands8.ring.4chip", 2**31 + 17, 1.0, False,
                    require_chip=False, bench=b)
    out[fault] = {k: r[k] for k in ("correct", "attempted", "failed",
                                    "metrics", "checks", "device")}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-c", RUNS], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_sound_run_through_the_service_is_correct(runs):
    r = runs["sound"]
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]
    assert r["attempted"] > 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"evals_per_s", "setup_s"}
    assert r["metrics"]["evals_per_s"]["value"] > 0


def test_a_run_without_the_ring_exchange_is_not_correct(runs):
    r = runs["ring_left_out"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert not r["correct"], r["checks"]
    assert r["checks"]["replay_gap"]["value"] > r["checks"]["replay_gap"]["limit"]


def test_the_cell_finds_its_driver_and_readers():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 4 and cell.config["driver"] == "served_solve"
    assert {m["name"] for m in cell.end_to_end()} == {"evals_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "device_idle_share.served_solve", "exchange_share.served_solve",
        "gen_roofline_share.served_solve"}


def test_the_capture_keeps_every_key_and_adds_scopes_and_generations(tmp_path):
    drv = harness.Cell(ROOT, CELL).driver()
    cap = drv.ScopeCapture(str(tmp_path), 0.0, 0.05, 10)
    step = jax.jit(lambda x: jax.lax.fori_loop(0, 200, lambda i, y: y * 0.5 + 1.0, x))
    x = step(jnp.ones((256, 256)))
    cap.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.2:
        x = step(x).block_until_ready()
    out = cap.result()
    assert {"window_s", "busy_s", "devices", "device_ops", "idle_gaps",
            "loops", "scopes", "generations"} <= set(out)
    assert out["window_s"] == pytest.approx(0.05, abs=0.03)
    assert not os.path.exists(os.path.join(str(tmp_path), ".bench_trace"))


# -- readers, on a synthetic record --------------------------------------------

def record(**trace):
    t = {"window_s": 0.25, "busy_s": 0.2, "devices": 4,
         "scopes": {"popt.migrate": 0.01, "popt.sync": 0.002,
                    "popt.variation": 0.15},
         "generations": 400.0}
    t.update(trace)
    return {"driver": "served_solve", "trace": t,
            "peak": peaks.peaks("TPU v5 lite"),
            "served_solve": {"fn": "shifted_rosenbrock", "pop": 800,
                             "dim": 1000, "islands": 8}}


def read(metric, rec):
    return harness.Cell(ROOT, CELL).reader(metric)(rec)


def test_idle_share_reader():
    assert read("device_idle_share.served_solve", record()) == pytest.approx(20.0)


def test_exchange_share_counts_migrate_and_sync():
    assert read("exchange_share.served_solve", record()) == pytest.approx(6.0)
    # a program without the sync scope counts what it has
    rec = record(scopes={"popt.migrate": 0.01, "popt.variation": 0.15})
    assert read("exchange_share.served_solve", rec) == pytest.approx(5.0)


def test_roofline_reader_counts_the_islands_one_chip_holds():
    least = work.least_time("shifted_rosenbrock", 800, 1000, 2,
                            peaks.peaks("TPU v5 lite"))
    got = read("gen_roofline_share.served_solve", record())
    assert got == pytest.approx(100.0 * least * 400.0 / 0.2)
    assert 3.0 < got < 3.3       # 12.8 MB a generation of two islands at 819 GB/s
    one_chip = read("gen_roofline_share.served_solve", record(devices=1))
    assert one_chip == pytest.approx(4 * got, rel=1e-3)


@pytest.mark.parametrize("metric", ["device_idle_share.served_solve",
                                    "exchange_share.served_solve",
                                    "gen_roofline_share.served_solve"])
def test_readers_read_nothing_elsewhere(metric):
    assert read(metric, dict(record(), driver="solve")) is None
    assert read(metric, dict(record(), trace=None)) is None
    assert read(metric, record(busy_s=0.0, window_s=0.0)) is None


def test_readers_without_their_part_of_the_trace_read_nothing():
    t = record()["trace"]
    plain = {k: v for k, v in t.items() if k not in ("scopes", "generations")}
    rec = dict(record(), trace=plain)
    assert read("exchange_share.served_solve", rec) is None
    assert read("gen_roofline_share.served_solve", rec) is None
    assert read("gen_roofline_share.served_solve", record(generations=0.0)) is None
    assert read("device_idle_share.served_solve", rec) == pytest.approx(20.0)
