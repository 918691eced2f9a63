"""Readings that the limits of a cell's check are set from; not part of a
benchmark run.

    python bench/control.py --workload table1.de_chunked --seeds 12 --control 3

Lower readings: the program's own numbers (``bench/check.py``) on each of
``--seeds`` seeds, through the cell's timed path at the cell's size: one
problem per seed. Upper
readings: the control, the reference computed one precision below the float32
the configuration states (bfloat16), put in the program's place for the first
``--control`` seeds' answers and compared by the same numbers. One JSON line
per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, harness, reference  # noqa: E402


def control_numbers(answers: list, rounds: int, replays: check.Replays) -> dict:
    """The bfloat16 reference in the program's place, for these answers'
    requests and seeds, against the float32 reference."""
    import jax.numpy as jnp
    value = replay = 0.0
    for a in answers:
        r = min(rounds, len(a.history))
        hist, arg = replays(a.cls, a.seed, r, jnp.bfloat16)
        f = reference.evaluate64(a.cls["fn"], arg)
        value = max(value, abs(float(hist[-1]) - f) / max(abs(f), 1.0))
        ctl = check.Answer(a.cls, a.seed, "done", float(hist[-1]), arg,
                           a.n_evals, np.concatenate([hist, a.history[r:]]))
        replay = max(replay, check.replay_gap(ctl, rounds, replays))
    return {"value_gap": value, "replay_gap": replay}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    cell = harness.Cell(ROOT, args.workload)
    harness.open_chip(ROOT, cell)
    cfg = cell.config
    rounds, n_sample = int(cfg["check"]["rounds"]), int(cfg["check"]["sample"])
    drv = cell.driver()
    seeds = [args.first_seed + i for i in range(args.seeds)]
    t0 = time.perf_counter()
    solve = drv.solver(cfg)
    solve(seeds[0] + 10_000)                            # warm
    per_seed = [[solve(s)] for s in seeds]
    print(json.dumps({"program_s": time.perf_counter() - t0}), flush=True)
    replays = check.Replays()
    for s, answers in zip(seeds, per_seed):
        nums = check.numbers(answers, s, rounds, n_sample, replays)
        print(json.dumps({"reading": "program", "seed": s,
                          "answers": len(answers), **nums}), flush=True)
    for s, answers in list(zip(seeds, per_seed))[: args.control]:
        done = check.sample([a for a in answers if a.status == "done"], s, n_sample)
        nums = control_numbers(done, rounds, replays)
        print(json.dumps({"reading": "control_bf16", "seed": s,
                          "answers": len(done), **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
