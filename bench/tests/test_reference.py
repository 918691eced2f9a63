"""The plain reference against the program at sizes a test run holds: the
float32 reference follows the program's trajectory, and the control, the
same reference in bfloat16, is far outside every limit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference

DE_T1 = {"w": 0.5, "px": 0.2, "strategy": "rand1bin", "barrier_mode": "chunked"}
CLASSES = {
    "de_chunked": dict(fn="shifted_rosenbrock", algo="de", dim=40, pop=32,
                       n_islands=1, migration="none", sync_every=10,
                       params=DE_T1, max_evals=32 * 61),
    "de_sphere": dict(fn="sphere", algo="de", dim=20, pop=16, n_islands=2,
                      sync_every=10, max_evals=2 * 16 * 41),
    "pso_fused_pallas": dict(fn="rastrigin", algo="pso", backend="pallas",
                             params={"fused": True}, dim=20, pop=16,
                             n_islands=2, sync_every=10, max_evals=2 * 16 * 41),
    "portfolio": dict(fn="ackley", portfolio=["de", "pso", "sa"], dim=20,
                      pop=16, n_islands=3, sync_every=10, max_evals=3 * 16 * 41),
    "de_asd": dict(fn="griewank", algo="de", dim=20, pop=16, n_islands=2,
                   sync_every=10, polish="asd", polish_every=2, polish_topk=2,
                   polish_steps=2, max_evals=2 * 16 * 41 + 2 * 2 * 2 * 88 * 2),
    "ring8": dict(fn="shifted_rosenbrock", algo="de", dim=40, pop=32,
                  n_islands=8, migration="ring", sync_every=10, params=DE_T1,
                  max_evals=8 * 32 * 41),
}
LIMIT = 1e-5      # the tightest limit a configuration sets


def _program(cls, seed):
    from repro.core.api import OptRequest
    from repro.core.scheduler import build_optimizer
    from repro.functions import get
    req = OptRequest.from_dict(dict(cls, seed=seed))
    r = build_optimizer(req).minimize(get(req.fn, req.dim), jax.random.PRNGKey(seed))
    return check.Answer(cls, seed, "done", float(r.value), np.asarray(r.arg),
                        int(r.n_evals), np.asarray(r.history))


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_reference_follows_the_program(name):
    cls = CLASSES[name]
    replays = check.Replays()
    for seed in (3, 2**31 + 5):
        a = _program(cls, seed % 2**31)
        nums = check.numbers([a], seed, 1000, 1, replays)
        assert nums["unanswered"] == 0 and nums["work_gap"] == 0
        assert nums["value_gap"] < LIMIT and nums["replay_gap"] < LIMIT, nums


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_bfloat16_control_fails(name):
    cls = CLASSES[name]
    replays = check.Replays()
    seed = 11
    n_rounds, _ = reference.budget(cls)
    hist, arg = replays(cls, seed, n_rounds, jnp.bfloat16)
    ctl = check.Answer(cls, seed, "done", float(hist[-1]), arg,
                       reference.budget(cls)[1], hist)
    nums = check.numbers([ctl], seed, 1000, 1, replays)
    assert nums["replay_gap"] > 100 * LIMIT, nums


def test_budget_matches_the_program():
    for cls in CLASSES.values():
        a = _program(cls, 0)
        assert reference.budget(cls) == (len(a.history), a.n_evals)
