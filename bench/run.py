"""Run one cell of the chip benchmark and print its result as one JSON line.

    python bench/run.py --workload table1.de_chunked --seed 7 --seconds 30 --trace 0

Every cell is driven by data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix; ``bench/configs/<config>.json`` holds the
deployment and names its driver (``bench/drivers/<driver>.py``);
``bench/traffic/<mix>.json`` holds the mix's parameters; each per-layer metric
is read by ``bench/metrics/<metric>.py``. The run sets up and warms every
program the cell uses, measures for ``--seconds``, checks what the window
produced against the plain reference in ``bench/reference.py``, and prints the
numbers it compared, each beside its limit, as its last lines on standard
error. The last line of standard output is the result object. Off the TPU, or
on fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for line in result.pop("_stderr"):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
