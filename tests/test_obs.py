"""The program's names for its work (``core/obs.py``): every policy's
generation carries the phase scopes in its ops' ``op_name`` metadata, the
engine's and the scheduler's host spans land in a profiler capture, and the
scopes change no op of the compiled program."""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from repro.core import de, ga, obs, pso, sa
from repro.core.api import OptRequest
from repro.core.executor import ExecutorConfig, make_batch_evaluator
from repro.core.islands import IslandConfig, IslandOptimizer
from repro.core.scheduler import ShapeBucketScheduler
from repro.functions import get

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src", "repro")
P, D = 16, 4
PHASES = {obs.VARIATION, obs.EVALUATE, obs.RETRY, obs.SELECT}

POLICIES = {
    "de_sync": (lambda f, ev: de.make(f, ev, P, D), PHASES),
    "de_chunked": (lambda f, ev: de.make(f, ev, P, D, barrier_mode="chunked",
                                         n_chunks=4), PHASES),
    "pso": (lambda f, ev: pso.make(f, ev, P, D), PHASES),
    "ga": (lambda f, ev: ga.make(f, ev, P, D), PHASES),
    "sa": (lambda f, ev: sa.make(f, ev, P, D), PHASES),
    "de_fused": (lambda f, ev: de.make(f, ev, P, D, fused=True, interpret=True),
                 {obs.VARIATION, obs.FUSED, obs.SELECT}),
}


def op_names(text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', text)


def scopes_in(text: str) -> set[str]:
    """Every ``popt.*`` scope named in an op's ``op_name`` (a scope entered
    under ``vmap`` reads ``vmap(popt.variation)``)."""
    return {s for n in op_names(text) for s in re.findall(r"popt\.[a-z_.]+", n)}


def round_loops(text: str) -> list[str]:
    """``op_name``s of the ``while`` ops directly under ``popt.round``."""
    return [n for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%while\S* = ", line)
            for n in op_names(line) if re.search(r"popt\.round\)*/while$", n)]


def ops_only(text: str) -> list[str]:
    """The computations and instructions of an HLO module, metadata
    stripped (the stack-frame tables that carry function names go too)."""
    out = []
    for line in text.splitlines():
        if (re.match(r"\s*(ROOT )?%\S+ = ", line)
                or re.match(r"\s*(ENTRY )?%\S+ .*\{\s*$", line)
                or line.strip() == "}"):
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return out


@pytest.mark.parametrize("name", sorted(POLICIES))
def test_one_generation_carries_the_phase_scopes(name):
    make, want = POLICIES[name]
    f = get("rastrigin", D)
    algo = make(f, make_batch_evaluator(f, ExecutorConfig(retry_bad=True)))
    step = algo.step_override or algo.gen
    key = jax.random.PRNGKey(0)
    text = jax.jit(step).lower(algo.init(key), key).compile().as_text()
    assert want <= scopes_in(text), (name, scopes_in(text))


def _whole_run_text(opt: IslandOptimizer, f) -> str:
    algo, run, pp = opt._single_fn(f)
    n_rounds = opt._budget(*opt._eval_totals(algo), pp)[0]
    state = opt._init_state(algo, jax.random.PRNGKey(0))
    args = [state, jax.random.split(jax.random.PRNGKey(1), n_rounds)]
    if opt._async:
        args += opt._materialize_schedule(n_rounds)
    return run.lower(*args).compile().as_text()


ENGINES = {
    # Table I's shape of run, small: one island of chunked DE
    "table1": (IslandConfig(pop=P, dim=D, sync_every=5, migration="none",
                            max_evals=P * 41),
               {"barrier_mode": "chunked", "n_chunks": 4},
               {obs.ROUND, obs.VARIATION, obs.EVALUATE, obs.RETRY, obs.SELECT}),
    "ring_polish": (IslandConfig(n_islands=2, pop=P, dim=D, sync_every=3,
                                 migration="ring", polish="asd",
                                 polish_topk=2, polish_steps=1,
                                 max_evals=2 * P * 40),
                    {}, {obs.ROUND, obs.MIGRATE, obs.POLISH}),
    "async_mailbox": (IslandConfig(n_islands=2, pop=P, dim=D, sync_every=3,
                                   migration="ring", sync_policy="async",
                                   max_evals=2 * P * 40),
                      {}, {obs.ROUND, obs.MIGRATE}),
}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_whole_run_names_its_rounds(name):
    """The round scope is on the whole-run program with the scan over a
    round's generations directly under it: its runs times ``sync_every``
    count the generations in a trace. (XLA's CPU backend may clone that loop
    where islands are stacked; the TPU's compile of Table I holds one,
    ``tests/test_tpu_compile.py``.)"""
    cfg, params, want = ENGINES[name]
    f = get("rastrigin", D)
    text = _whole_run_text(IslandOptimizer(de.make, cfg, params=params), f)
    assert want <= scopes_in(text), scopes_in(text)
    assert round_loops(text)
    if cfg.n_islands == 1:
        assert len(round_loops(text)) == 1, round_loops(text)


def test_scopes_change_no_op(monkeypatch):
    """The same run compiled with the scopes and with every scope a no-op is
    the same program, op for op, once metadata is stripped."""
    cfg, params, _ = ENGINES["table1"]
    f = get("rastrigin", D)
    scoped = _whole_run_text(IslandOptimizer(de.make, cfg, params=params), f)
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    bare = _whole_run_text(IslandOptimizer(de.make, cfg, params=params), f)
    assert not scopes_in(bare)
    assert ops_only(scoped) == ops_only(bare)


def _host_spans(tmp_path, body) -> set[str]:
    body()                                   # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        body()
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return {e.name for pl in ProfileData.from_file(path).planes
            if pl.name.startswith("/host:") for ln in pl.lines
            for e in ln.events if e.name.startswith("popt.")}


def test_a_capture_of_minimize_holds_the_engine_spans(tmp_path):
    f = get("sphere", D)
    opt = IslandOptimizer(de.make, IslandConfig(pop=8, dim=D, sync_every=5,
                                                max_evals=8 * 21))
    spans = _host_spans(tmp_path, lambda: opt.minimize(f, jax.random.PRNGKey(0)))
    assert {obs.ENGINE_INIT, obs.ENGINE_DISPATCH, obs.ENGINE_FETCH} <= spans


def test_a_capture_of_a_served_bucket_holds_the_scheduler_spans(tmp_path):
    req = OptRequest(fn="sphere", dim=D, pop=8, max_evals=8 * 31, sync_every=5)

    def serve():
        sched = ShapeBucketScheduler(workers=1)
        try:
            assert sched.result(sched.submit(req)).status == "done"
        finally:
            sched.close()
            for t in sched._threads:       # the run span ends after the result
                t.join(timeout=60)
                assert not t.is_alive()

    spans = _host_spans(tmp_path, serve)
    assert {obs.SCHED_RUN, obs.ENGINE_STEP, obs.SCHED_PROGRESS} <= spans


SYNC_PROGRAMS = """
import json, re
import jax
from repro.core import de
from repro.core.islands import IslandConfig, IslandOptimizer
from repro.core.mesh import MeshConfig
from repro.functions import get

f = get("rastrigin", 4)
out = {}
for policy in ("barrier", "async"):
    cfg = IslandConfig(n_islands=4, pop=16, dim=4, sync_every=3,
                       migration="ring", share_incumbent=True,
                       sync_policy=policy, max_evals=4 * 16 * 40)
    for devices in (4, 1):
        opt = IslandOptimizer(de.make, cfg, mesh_cfg=MeshConfig(devices))
        _, many, _ = opt._many_fn(f)
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        args = [keys]
        if opt._async:
            args += opt._materialize_schedule(opt._budget(
                *opt._eval_totals(opt._build(f)))[0])
        text = many.lower(*args).compile().as_text()
        synced = [line for line in text.splitlines()
                  if re.search(r'op_name="[^"]*popt\\.sync', line)]
        out[f"{policy}/{devices}"] = [len(synced), sorted(
            {k for line in synced
             for k in re.findall(r" (all-reduce|all-gather)(?:-start)?\\(",
                                 line)})]
print(json.dumps(out))
"""


def test_sync_scope_names_the_cross_chip_merge_and_only_that():
    """``popt.sync`` is on the sharded program's cross-device merge of the
    incumbent (the per-round ``pmin`` of the history point, the
    ``share_incumbent`` all-gathers), barrier and async alike, and on no op
    of the same run on a mesh of one device. The sharded programs need four
    devices, so they compile in a child process that sees four CPU
    devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-c", SYNC_PROGRAMS], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    synced = json.loads(p.stdout.strip().splitlines()[-1])
    for policy in ("barrier", "async"):
        assert synced[f"{policy}/4"][1] == ["all-gather", "all-reduce"], synced
        assert synced[f"{policy}/1"][0] == 0, synced


def test_every_name_lives_in_the_vocabulary_and_is_used():
    """Nothing under ``src/`` opens a scope or span but through ``obs``, and
    every name ``obs`` holds is entered somewhere."""
    names = obs.SCOPES + obs.SPANS
    assert len(set(names)) == len(names)
    assert all(n.startswith("popt.") for n in names)
    consts = {k for k, v in vars(obs).items() if v in names and k.isupper()}
    used: set[str] = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        text = open(path).read()
        if os.path.basename(path) == "obs.py":
            continue
        assert "named_scope(" not in text and "TraceAnnotation(" not in text, path
        used |= set(re.findall(r"obs\.(?:scope|span)\(obs\.([A-Z_]+)\)", text))
    assert used == consts
