"""Device-scaling benchmark for the sharded island engine (DESIGN.md §8).

Runs the same island DE configuration with the island axis laid over 1, 2, 4
and 8 devices (``core.mesh.MeshConfig``) and records *round throughput* —
sync rounds per second of the compiled run, excluding compilation — plus the
speedup over the 1-device (unsharded-engine) baseline. On a machine without
accelerators the mesh is host-platform devices: the script sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` itself (before jax
loads) unless the flag is already present, which is also how the CI
distributed-smoke job runs it.

Writes ``BENCH_distributed.json`` (the repo's scaling artifact; CI uploads
the --smoke variant) and exits non-zero unless the widest mesh beats the
1-device baseline by ``--min-speedup`` on at least one function.

    PYTHONPATH=src python benchmarks/distributed.py            # full
    PYTHONPATH=src python benchmarks/distributed.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import json
import os
import time

MAX_DEVICES = 8
_FLAG = "xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --{_FLAG}={MAX_DEVICES}").strip()

import jax  # noqa: E402  (after XLA_FLAGS so host devices exist)

from repro.core import ALGORITHMS, IslandConfig, IslandOptimizer, MeshConfig  # noqa: E402
from repro.functions import get  # noqa: E402


def time_devices(fn: str, devices: int, *, islands: int, pop: int, dim: int,
                 sync_every: int, budget: int, repeats: int) -> dict:
    """Median wall time of a compiled run on a ``devices``-wide mesh."""
    f = get(fn, dim)
    cfg = IslandConfig(n_islands=islands, pop=pop, dim=dim,
                       sync_every=sync_every, migration="ring",
                       max_evals=budget)
    opt = IslandOptimizer(
        ALGORITHMS["de"], cfg,
        mesh_cfg=MeshConfig(devices=devices) if devices > 1 else None)
    key = jax.random.PRNGKey(0)
    res = opt.minimize(f, key)              # compile + warm the caches
    n_rounds = res.n_gens // sync_every
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        opt.minimize(f, key)
        walls.append(time.perf_counter() - t0)
    wall = sorted(walls)[len(walls) // 2]
    return {
        "devices": devices,
        "wall_s": round(wall, 4),
        "rounds_per_s": round(n_rounds / wall, 2),
        "n_rounds": n_rounds,
        "best": res.value,
    }


def bench(functions: list[str], device_counts: list[int], **sizes) -> list[dict]:
    rows = []
    for fn in functions:
        base = None
        for d in device_counts:
            r = time_devices(fn, d, **sizes)
            base = base or r["rounds_per_s"]
            r["fn"] = fn
            r["speedup"] = round(r["rounds_per_s"] / base, 3)
            rows.append(r)
            print(f"{fn:12s} devices={d}  {r['rounds_per_s']:9.2f} rounds/s  "
                  f"({r['speedup']:.2f}x vs 1 device)")
    return rows


def straggler_bench(fn: str, *, islands: int, pop: int, dim: int,
                    sync_every: int, rounds: int, slow_factor: int = 4,
                    base_ms: float = 25.0) -> dict:
    """Straggler study (ISSUE 8 satellite): one island is ``slow_factor``x
    slower than the rest; compare **island-round throughput** of the barrier
    engine vs the async staleness-bounded engine under the same fault.

    The fault is injected host-side, not modeled analytically: both runs
    step one job through the engine's host-stepped runner
    (``BucketStepper``), where a sleep after each round stands in for the
    slow island's extra compute.

    The straggler's step time is calibrated from a faultless timed run:
    ``fast_step = max(base_ms, measured per-tick compute)`` and the slow
    island takes ``slow_factor * fast_step``.

    * Barrier: the ``lax.ppermute`` round is a global barrier, so EVERY round
      waits the straggler's full step on top of its own compute — the
      host loop sleeps ``slow_factor*fast_step`` once per round, and all
      ``islands`` islands advance per round.
    * Async: the mailbox engine lets the fast islands tick at their own
      cadence — the host loop sleeps one ``fast_step`` per *tick*, and the
      straggler island steps only every ``slow_factor`` ticks
      (``AsyncSchedule.from_cadences``), exactly as many generations per
      wall-second as its 4x-slow hardware would manage.

    Reported throughput is island-rounds/second: how many island round-steps
    the federation completes per wall-clock second. The acceptance bar
    (async >= 2x barrier under a 4x straggler) is asserted by ``main``.
    """
    import dataclasses

    from repro.core import AsyncSchedule

    f = get(fn, dim)
    budget = islands * pop * (rounds * sync_every + 1)
    cfg_b = IslandConfig(n_islands=islands, pop=pop, dim=dim,
                         sync_every=sync_every, migration="ring",
                         max_evals=budget)
    cfg_a = dataclasses.replace(cfg_b, sync_policy="async",
                                max_staleness=slow_factor)

    def run(cfg, schedule, sleep_s):
        opt = IslandOptimizer(ALGORITHMS["de"], cfg, schedule=schedule)
        stepper = opt.bucket_stepper(f)
        keys = jax.random.PRNGKey(0)[None]

        def one_run():
            state, round_keys = stepper.init(keys)
            for r in range(stepper.n_rounds):
                state, point = stepper.step(state, round_keys, r)
                point.block_until_ready()
                time.sleep(sleep_s)

        one_run()                                     # compile/warm
        t0 = time.perf_counter()
        one_run()
        wall = time.perf_counter() - t0
        return opt, wall

    # Calibrate the fast islands' step time: a faultless timed run gives the
    # engine's own per-tick compute, and the straggler's step is modeled as
    # ``slow_factor`` times that (floored at base_ms so a toy config still
    # injects a visible fault). The barrier round then waits the straggler's
    # FULL step on top of its own compute; the async tick only ever waits the
    # fast step.
    _, wall_0 = run(cfg_b, None, 0.0)
    # 1.5x the measured tick keeps the injected fault dominant over the
    # host-stepped loop's dispatch overhead (which both engines pay alike).
    fast_step = max(base_ms / 1e3, 1.5 * wall_0 / rounds)
    _, wall_b = run(cfg_b, None, slow_factor * fast_step)
    sync_tp = islands * rounds / wall_b

    cadences = [1] * (islands - 1) + [slow_factor]    # island -1 is 4x slow
    sched = AsyncSchedule.from_cadences(cadences, rounds)
    opt_a, wall_a = run(cfg_a, sched, fast_step)
    step_m, _ = opt_a.recorded_schedule.materialize(rounds, islands)
    async_tp = float(step_m.sum()) / wall_a

    row = {
        "fn": fn, "islands": islands, "slow_factor": slow_factor,
        "base_ms": base_ms, "rounds": rounds,
        "sync_wall_s": round(wall_b, 4),
        "async_wall_s": round(wall_a, 4),
        "sync_island_rounds_per_s": round(sync_tp, 2),
        "async_island_rounds_per_s": round(async_tp, 2),
        "async_over_sync": round(async_tp / sync_tp, 3),
    }
    print(f"straggler {fn:12s} sync {sync_tp:8.2f} island-rounds/s | "
          f"async {async_tp:8.2f} | {row['async_over_sync']:.2f}x")
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: fewer rounds/repeats, widest mesh only")
    ap.add_argument("--functions", nargs="+",
                    default=["rastrigin", "rosenbrock"])
    ap.add_argument("--islands", type=int, default=8)
    ap.add_argument("--pop", type=int, default=512)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--sync-every", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=60,
                    help="sync rounds per timed run (sets the eval budget)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="fail unless the widest mesh strictly beats this "
                         "on at least one function")
    ap.add_argument("--straggler-rounds", type=int, default=40,
                    help="ticks/rounds in the straggler study")
    ap.add_argument("--min-straggler-ratio", type=float, default=2.0,
                    help="fail unless async island-round throughput beats "
                         "the barrier engine by this under a 4x straggler")
    ap.add_argument("--out", default="BENCH_distributed.json")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    counts = [d for d in (1, 2, 4, 8) if d <= min(n_dev, args.islands)]
    if args.smoke:
        args.rounds, args.repeats = 25, 2
        args.straggler_rounds = 16
        counts = [1, counts[-1]] if counts[-1] > 1 else counts

    budget = args.islands * args.pop * (args.rounds * args.sync_every + 1)
    rows = bench(args.functions, counts,
                 islands=args.islands, pop=args.pop, dim=args.dim,
                 sync_every=args.sync_every, budget=budget,
                 repeats=args.repeats)

    straggler = straggler_bench(
        args.functions[0], islands=args.islands, pop=min(args.pop, 64),
        dim=args.dim, sync_every=args.sync_every,
        rounds=args.straggler_rounds)

    widest = counts[-1]
    best_by_fn = {fn: max(r["speedup"] for r in rows
                          if r["fn"] == fn and r["devices"] == widest)
                  for fn in args.functions}
    best = max(best_by_fn.values())
    rec = {
        "algo": "de", "migration": "ring", "islands": args.islands,
        "pop": args.pop, "dim": args.dim, "sync_every": args.sync_every,
        "rounds": args.rounds, "device_counts": counts,
        "backend": jax.default_backend(), "visible_devices": n_dev,
        "smoke": args.smoke, "rows": rows,
        "speedup_at_widest_by_fn": best_by_fn,
        "best_speedup": best,
        "straggler": straggler,
    }
    with open(args.out, "w") as fh:
        json.dump(rec, fh, indent=2)
        fh.write("\n")
    print(f"\nbest {widest}-device speedup over the unsharded engine: "
          f"{best:.2f}x -> {args.out}")
    if best <= args.min_speedup:
        raise SystemExit(
            f"no function scaled past {args.min_speedup}x at {widest} devices")
    if straggler["async_over_sync"] < args.min_straggler_ratio:
        raise SystemExit(
            f"async throughput under a 4x straggler was only "
            f"{straggler['async_over_sync']}x the barrier engine's "
            f"(need >= {args.min_straggler_ratio}x)")


if __name__ == "__main__":
    main()
