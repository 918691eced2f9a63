"""Whether what the window produced is correct, by comparison with the plain
reference (``bench/reference.py``), each number against a limit of its own
from the configuration file's ``"check"`` block.

An answer is one finished problem or job: its request class, seed, status,
value, argument, evaluation count and per-round history. The numbers:

``unanswered``  answers that never came, failed or are not finite (limit 0);
``work_gap``    over every answer, how far the rounds run and the evaluations
                counted are from what the request's budget buys (exact, 0);
``value_gap``   over every answer, |value - f(arg)| / max(|f(arg)|, 1) with
                f recomputed in float64 on the host;
``replay_gap``  over a sample drawn from the seed, with the costliest answer
                in it, the widest relative gap between the answer's history
                over its first ``rounds`` sync rounds and the reference's
                replay of the same request and seed; where the replay covers
                every round, also between the answer's value and argument and
                the reference's.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Any

import numpy as np

from bench import reference, traffic


@dataclasses.dataclass
class Answer:
    cls: dict                 # the request's fields (OptRequest names)
    seed: int
    status: str               # "done" or what the service said
    value: float = math.nan
    arg: Any = None
    n_evals: int = -1
    history: Any = None


class Replays:
    """Compiled reference replays, one per request class, rounds and dtype."""

    def __init__(self) -> None:
        self._fns: dict = {}

    def __call__(self, cls: dict, seed: int, rounds: int, dtype=None):
        import jax
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        k = (json.dumps(cls, sort_keys=True), rounds, jnp.dtype(dtype).name)
        if k not in self._fns:
            self._fns[k] = reference.make_replay(cls, rounds, dtype)
        hist, arg = self._fns[k](jax.random.PRNGKey(seed))
        return np.asarray(hist), np.asarray(arg)


def sample(answers: list[Answer], seed: int, n: int) -> list[Answer]:
    """``n`` done answers drawn from the seed, the costliest always among
    them."""
    done = [a for a in answers if a.status == "done"]
    if len(done) <= n:
        return done
    cost = np.asarray([reference.budget(a.cls)[1] for a in done])
    order = traffic.rng(seed * 31 + 5).permutation(len(done))
    first = int(np.argmax(cost))
    picked = [first] + [int(i) for i in order if i != first][: n - 1]
    return [done[i] for i in picked]


def numbers(answers: list[Answer], seed: int, rounds: int, n_sample: int,
            replays: Replays | None = None, dtype=None) -> dict[str, float]:
    """The compared numbers of a set of answers (see the module docstring)."""
    replays = replays or Replays()
    ok = [a for a in answers if a.status == "done" and np.isfinite(a.value)]
    bad = len(answers) - len(ok)
    work = value = replay = 0.0
    for a in ok:
        n_rounds, n_evals = reference.budget(a.cls)
        work = max(work, abs(len(a.history) - n_rounds) + abs(a.n_evals - n_evals))
        f = reference.evaluate64(a.cls["fn"], a.arg)
        value = max(value, abs(a.value - f) / max(abs(f), 1.0))
    for a in sample(ok, seed, n_sample):
        replay = max(replay, replay_gap(a, rounds, replays, dtype))
    return {"unanswered": float(bad), "work_gap": float(work),
            "value_gap": value, "replay_gap": replay}


def replay_gap(a: Answer, rounds: int, replays: Replays, dtype=None) -> float:
    """One answer's gap to the reference's replay of its first ``rounds``."""
    r = min(rounds, len(a.history))
    hist, arg = replays(a.cls, a.seed, r, dtype)
    gap = reference.rel_gap(np.asarray(a.history)[:r], hist)
    if r == len(a.history):
        gap = max(gap, reference.rel_gap([a.value], hist[-1:]),
                  reference.rel_gap(a.arg, arg))
    return gap


def check(config: dict, answers: list[Answer], seed: int) -> dict:
    """``{"correct", "numbers": {name: {"value", "limit"}}, "lines"}``: every
    number at or under its limit, and at least one answer."""
    c = config["check"]
    nums = numbers(answers, seed, int(c["rounds"]), int(c["sample"]))
    limits = dict(c["limits"], unanswered=0.0, work_gap=0.0)
    out = {k: {"value": v, "limit": float(limits[k])} for k, v in nums.items()}
    correct = bool(answers) and all(
        np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in out.values())
    lines = [f"answers: {len(answers)}"] + [
        f"check {k}: {v['value']!r} limit {v['limit']!r}" for k, v in out.items()]
    lines.append(f"correct: {correct}")
    return {"correct": correct, "numbers": out, "lines": lines}
