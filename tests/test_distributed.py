"""Distributed island layer tests (DESIGN.md §8).

Two tiers, matching the determinism contract:

* 1-device-mesh tests run everywhere (tier-1): the ``shard_map`` program on a
  degenerate mesh must be bit-identical to the unsharded engine.
* 8-host-device tests (``ppermute`` ring vs the host-side roll reference,
  sharded engine vs unsharded, sharded scheduler buckets) skip unless the
  process sees >= 8 devices — CI's distributed-smoke job provides them with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; conftest.py
  deliberately does NOT force them for the rest of the suite.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from repro.core import (ALGORITHMS, IslandConfig, IslandOptimizer, MeshConfig,
                        OptRequest, ShapeBucketScheduler)
from repro.core import mesh as mesh_mod
from repro.core import migration
from repro.functions import get

KEY = jax.random.PRNGKey(7)
N_DEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _cfg(**kw):
    base = dict(n_islands=4, pop=16, dim=6, sync_every=5, migration="ring",
                max_evals=4000)
    base.update(kw)
    return IslandConfig(**base)


def _minimize(algo, cfg, f, mesh_cfg=None, key=KEY):
    return IslandOptimizer(ALGORITHMS[algo], cfg,
                           mesh_cfg=mesh_cfg).minimize(f, key)


def _assert_same(a, b):
    """Bit-identical OptimizeResults: value, accounting, arg and history."""
    assert a.value == b.value
    assert a.n_evals == b.n_evals and a.n_gens == b.n_gens
    assert np.array_equal(np.asarray(a.arg), np.asarray(b.arg))
    assert np.array_equal(np.asarray(a.history), np.asarray(b.history))


# --- determinism contract: 1-device mesh == unsharded engine (tier-1) -------

@pytest.mark.parametrize("algo", ["de", "ga", "pso"])
def test_one_device_mesh_bit_identical(algo):
    f = get("rastrigin", 6)
    cfg = _cfg(migration="starvation" if algo == "ga" else "ring")
    _assert_same(_minimize(algo, cfg, f),
                 _minimize(algo, cfg, f, mesh_cfg=MeshConfig(devices=1)))


def test_one_device_mesh_share_incumbent_and_polish_bit_identical():
    f = get("rosenbrock", 6)
    cfg = _cfg(share_incumbent=True, max_evals=6000,
               polish="asd", polish_every=2, polish_topk=2, polish_steps=2)
    _assert_same(_minimize("de", cfg, f),
                 _minimize("de", cfg, f, mesh_cfg=MeshConfig(devices=1)))


def test_one_device_mesh_minimize_many_bit_identical():
    f = get("sphere", 6)
    cfg = _cfg()
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (0, 3, 11)])
    plain = IslandOptimizer(ALGORITHMS["de"], cfg).minimize_many(f, keys)
    shard = IslandOptimizer(ALGORITHMS["de"], cfg,
                            mesh_cfg=MeshConfig(devices=1)).minimize_many(f, keys)
    for a, b in zip(plain, shard):
        _assert_same(a, b)


# --- migration primitives: sharded forms vs host-side references ------------

@needs8
@pytest.mark.parametrize("devices", [4, 8])   # islands/shard = 2 and 1
def test_ppermute_ring_matches_host_ring(devices):
    I, P, D, k = 8, 6, 4, 2
    kp, kf = jax.random.split(KEY)
    pop = jax.random.uniform(kp, (I, P, D), minval=-1.0, maxval=1.0)
    fit = jax.random.uniform(kf, (I, P), minval=0.0, maxval=9.0)
    ref_pop, ref_fit = migration.ring(pop, fit, k=k)

    mc = MeshConfig(devices=devices)
    sharded = mesh_mod.shard_map(
        lambda p, f: migration.ring(p, f, k=k, axis=mc.axis, n_shards=devices),
        mc.build(), in_specs=(PS(mc.axis), PS(mc.axis)),
        out_specs=(PS(mc.axis), PS(mc.axis)))
    got_pop, got_fit = sharded(pop, fit)
    assert np.array_equal(np.asarray(got_pop), np.asarray(ref_pop))
    assert np.array_equal(np.asarray(got_fit), np.asarray(ref_fit))


@needs8
def test_allgather_starvation_matches_host():
    I, P, D = 8, 10, 3
    kp, kf = jax.random.split(KEY)
    pop = jax.random.uniform(kp, (I, P, D), minval=-1.0, maxval=1.0)
    fit = jax.random.uniform(kf, (I, P), minval=0.0, maxval=9.0)
    # starve island 5: mark most of its population dead (+inf fitness)
    fit = fit.at[5, 1:].set(jnp.inf)
    ref_pop, ref_fit = migration.starvation(pop, fit, k=2)

    mc = MeshConfig(devices=8)
    sharded = mesh_mod.shard_map(
        lambda p, f: migration.starvation(p, f, k=2, axis=mc.axis, n_shards=8),
        mc.build(), in_specs=(PS(mc.axis), PS(mc.axis)),
        out_specs=(PS(mc.axis), PS(mc.axis)))
    got_pop, got_fit = sharded(pop, fit)
    assert np.array_equal(np.asarray(got_pop), np.asarray(ref_pop))
    assert np.array_equal(np.asarray(got_fit), np.asarray(ref_fit))


# --- sharded engine end-to-end (8 host devices) ------------------------------

@needs8
@pytest.mark.parametrize("mig,share", [("ring", False), ("starvation", False),
                                       ("ring", True)])
def test_eight_device_engine_matches_unsharded(mig, share):
    f = get("rastrigin", 6)
    cfg = _cfg(n_islands=8, migration=mig, share_incumbent=share,
               max_evals=8000)
    _assert_same(_minimize("de", cfg, f),
                 _minimize("de", cfg, f, mesh_cfg=MeshConfig(devices=8)))


@needs8
def test_eight_device_minimize_many_matches_sequential():
    f = get("levy", 6)
    cfg = _cfg(n_islands=8, max_evals=6000)
    seeds = (0, 4, 9)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    opt = IslandOptimizer(ALGORITHMS["de"], cfg, mesh_cfg=MeshConfig(devices=8))
    many = opt.minimize_many(f, keys)
    for s, got in zip(seeds, many):
        _assert_same(_minimize("de", cfg, f, key=jax.random.PRNGKey(s)), got)


@needs8
def test_scheduler_runs_sharded_bucket():
    """devices=8 jobs run in their own bucket and stay bit-identical to
    standalone sharded minimize; single-device traffic is undisturbed."""
    base = dict(fn="rastrigin", algo="de", dim=6, pop=16, n_islands=8,
                sync_every=5, max_evals=6000, migration="ring")
    sched = ShapeBucketScheduler()
    sharded_ids = [sched.submit(OptRequest(seed=s, devices=8, **base))
                   for s in (0, 2)]
    plain_id = sched.submit(OptRequest(seed=0, **base))
    assert len(sched.pending_buckets()) == 2
    assert sched.flush() == 3
    assert sched.n_dispatches == 2
    cfg = _cfg(n_islands=8, max_evals=6000)
    f = get("rastrigin", 6)
    for jid, seed in zip(sharded_ids, (0, 2)):
        got = sched.result(jid)
        assert got.status == "done"
        expect = _minimize("de", cfg, f, mesh_cfg=MeshConfig(devices=8),
                           key=jax.random.PRNGKey(seed))
        assert got.result.value == expect.value
        assert np.array_equal(np.asarray(got.result.arg),
                              np.asarray(expect.arg))
    assert sched.result(plain_id).status == "done"


# --- the served ring on four devices, in a child process (tier-1) ----------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVED_RING4 = """
import json, sys
import numpy as np
from bench import check
from repro.launch.opt_serve import OptimizationService

cfg = json.load(open("bench/configs/cec2008_f3_islands8_ring.json"))
req = dict(cfg["request"], dim=40, pop=32, max_evals=8 * 32 * 21)
out = {}
for devices in (4, 1):
    service = OptimizationService(max_batch=1, workers=0)
    reply = service.handle({"op": "submit",
                            "request": dict(req, devices=devices, seed=9)})
    resp = service.scheduler.result(reply["id"], evict=True)
    assert resp.status == "done", resp.error
    out[devices] = resp.result
a, b = out[4], out[1]
ans = check.Answer(req, 9, "done", a.value, np.asarray(a.arg), a.n_evals,
                   np.asarray(a.history))
print(json.dumps({
    "rounds": len(a.history),
    "history_same": np.array_equal(a.history, b.history),
    "arg_same": np.array_equal(np.asarray(a.arg), np.asarray(b.arg)),
    "value_same": a.value == b.value,
    "numbers": check.numbers([ans], 9, len(a.history), 1),
    "limits": cfg["check"]["limits"]}))
"""


def _four_device_child(code: str) -> dict:
    """Run ``code`` in a child process that sees four CPU devices (this
    process sees one) and return the JSON of its last line of output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_served_ring_on_four_devices_matches_one_device_and_the_reference():
    """The four-chip cell's request, at pop 32 and dim 40, submitted through
    ``OptimizationService``: the sharded bucket (two islands per device,
    ring migration across devices) gives the history and argument of the
    same request at ``devices=1``, and the plain reference's replay of the
    whole run agrees within the configuration's limits."""
    r = _four_device_child(SERVED_RING4)
    assert r["rounds"] == 2
    assert r["history_same"] and r["arg_same"] and r["value_same"]
    nums, limits = r["numbers"], r["limits"]
    assert nums["unanswered"] == 0 and nums["work_gap"] == 0, nums
    assert nums["value_gap"] <= limits["value_gap"], nums
    assert nums["replay_gap"] <= limits["replay_gap"], nums


# --- request plumbing and validation (device-count independent) -------------

def test_devices_joins_shape_class():
    a = OptRequest(fn="sphere", n_islands=8, devices=1)
    b = OptRequest(fn="sphere", n_islands=8, devices=8)
    assert a.shape_class() != b.shape_class()
    assert (OptRequest(fn="sphere", n_islands=8, devices=8, seed=0).shape_class()
            == OptRequest(fn="sphere", n_islands=8, devices=8, seed=5).shape_class())
    # JSONL requests pass the field through unchanged
    assert OptRequest.from_dict({"fn": "sphere", "devices": 4}).devices == 4


def test_unplaceable_devices_error_is_isolated_per_bucket():
    sched = ShapeBucketScheduler()
    bad = sched.submit(OptRequest(fn="sphere", dim=4, pop=16, n_islands=4,
                                  max_evals=1000, devices=4096))
    ok = sched.submit(OptRequest(fn="sphere", dim=4, pop=16, max_evals=1000))
    sched.flush()
    assert sched.poll(bad).status == "error"
    assert "devices" in sched.poll(bad).error
    assert sched.poll(ok).status == "done"


def test_meshconfig_validation():
    with pytest.raises(ValueError, match="devices"):
        MeshConfig(devices=0).build()
    with pytest.raises(ValueError, match="visible"):
        MeshConfig(devices=100_000).build()
    with pytest.raises(ValueError, match="multiple"):
        MeshConfig(devices=3).local_islands(4)
    assert MeshConfig(devices=2).local_islands(8) == 4
    assert mesh_mod.ring_perm(3) == [(0, 1), (1, 2), (2, 0)]


def test_island_optimizer_rejects_bad_sharding_configs():
    f = get("sphere", 4)
    with pytest.raises(ValueError, match="n_islands > 1"):
        IslandOptimizer(ALGORITHMS["de"], _cfg(n_islands=1, migration="none"),
                        mesh_cfg=MeshConfig(devices=1))
    with pytest.raises(ValueError, match="multiple"):
        IslandOptimizer(ALGORITHMS["de"], _cfg(n_islands=4),
                        mesh_cfg=MeshConfig(devices=3))
    with pytest.raises(ValueError, match="mutually exclusive"):
        IslandOptimizer(ALGORITHMS["de"], _cfg(),
                        mesh=mesh_mod.MeshConfig(devices=1).build(),
                        mesh_cfg=MeshConfig(devices=1))
    opt = IslandOptimizer(ALGORITHMS["de"], _cfg(),
                          mesh_cfg=MeshConfig(devices=1))
    with pytest.raises(ValueError, match="unsharded engine"):
        opt.bucket_stepper(f)
