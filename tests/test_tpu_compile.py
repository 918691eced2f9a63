"""Compile the five fused kernels at Table I width (800x1000) for a described
TPU v5e chip, with the tile the autotuner picks for the TPU; Table I's
whole-run program, whose phase scopes must change no op and whose chunks
build only their own rows; and eight Table I islands sharded over the
described four chips, as the scheduler runs them.

No chip is needed: the TPU compiler runs here against a described topology
and raises what Mosaic would raise on the chip (tiling, layouts, VMEM, and
primitives it cannot lower). Every case must compile to a real Mosaic kernel
(``tpu_custom_call``), never to interpreted ops.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, and every test worker imports
this file.
"""
import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import mesh as mesh_mod
from repro.core import obs
from repro.core.api import OptRequest
from repro.core.scheduler import build_optimizer
from repro.functions import get
from repro.kernels import autotune
from repro.kernels.bench_eval import bench_eval
from repro.kernels.de_step import de_step
from repro.kernels.eval_select import eval_select
from repro.kernels.ga_step import ga_step
from repro.kernels.pso_step import pso_step

P, D = 800, 1000          # Table I: population 800, 1000-D

CASES = ([(k, t) for k in ("bench_eval", "de_step")
          for t in ("shifted_rosenbrock", "rastrigin", "griewank")]
         + [(k, "rastrigin") for k in ("pso_step", "ga_step", "eval_select")])


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent cache off around the
    compiles (an entry written for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


def _entry(kind, tag, kc):
    """(kernel call, operand shapes) for one kernel at (P, D)."""
    f32, i32 = jnp.float32, jnp.int32
    pd, p, d = ((P, D), f32), ((P,), f32), ((D,), f32)
    if kind == "bench_eval":
        return (lambda x, s: bench_eval(x, tag, shift=s, kernel_cfg=kc),
                [pd, d])
    if kind == "de_step":
        return (lambda *a: de_step(*a[:-1], fn=tag, shift=a[-1],
                                   kernel_cfg=kc),
                [pd, p, ((3, P), i32), pd, ((P,), i32), d])
    if kind == "pso_step":
        return (lambda *a: pso_step(*a[:-1], fn=tag, shift=a[-1],
                                    kernel_cfg=kc),
                [pd, pd, pd, p, pd, pd, d, d])
    if kind == "ga_step":
        return (lambda *a: ga_step(*a[:-1], fn=tag, shift=a[-1],
                                   kernel_cfg=kc),
                [pd, pd, pd, p, ((P,), i32), p, pd, pd, d])
    return (lambda *a: eval_select(*a[:-1], fn=tag, shift=a[-1],
                                   kernel_cfg=kc),
            [pd, p, pd, p, d])


@pytest.mark.parametrize("kind,tag", CASES)
def test_kernel_compiles_for_v5e(kind, tag, one_chip):
    kc = autotune.choose(kind, P, D, tag, interpret=False)
    assert kc.interpret is False
    call, shapes = _entry(kind, tag, kc)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, (kind, tag, kc)


TABLE1 = OptRequest(fn="shifted_rosenbrock", algo="de", backend="xla", dim=D,
                    pop=P, n_islands=1, migration="none", sync_every=10,
                    max_evals=P * 20_001,
                    params=(("w", 0.5), ("px", 0.2), ("strategy", "rand1bin"),
                            ("barrier_mode", "chunked")))


def _table1_text(one_chip) -> str:
    """Table I's whole-run program (``minimize``'s one dispatch) compiled
    for the described chip."""
    opt, f = build_optimizer(TABLE1), get(TABLE1.fn, TABLE1.dim)
    algo, run, pp = opt._single_fn(f)
    n_rounds = opt._budget(*opt._eval_totals(algo), pp)[0]
    state = jax.eval_shape(algo.init, jax.random.PRNGKey(0))
    args = (jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), state),
            jax.ShapeDtypeStruct((n_rounds, 2), jnp.uint32, sharding=one_chip))
    return run.lower(*args).compile().as_text()


def _ops(text: str) -> list[str]:
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%\S+ = ", line)
            or re.match(r"\s*(ENTRY )?%\S+ .*\{\s*$", line)]


def test_table1_program_names_its_phases_and_changes_no_op(one_chip,
                                                           monkeypatch):
    """On the chip's compiler Table I's program carries every phase scope,
    holds exactly one ``while`` directly under ``popt.round`` (the scan over
    a round's generations, which counts generations in a trace), and is the
    same program op for op as with every scope a no-op."""
    text = _table1_text(one_chip)
    names = re.findall(r'op_name="([^"]*)"', text)
    scopes = {s for n in names for s in re.findall(r"popt\.[a-z_.]+", n)}
    assert {obs.ROUND, obs.VARIATION, obs.EVALUATE, obs.RETRY,
            obs.SELECT} <= scopes
    loops = [n for line in text.splitlines()
             if re.match(r"\s*(ROOT )?%while\S* = ", line)
             for n in re.findall(r'op_name="([^"]*)"', line)
             if n.endswith(obs.ROUND + "/while")]
    assert len(loops) == 1, loops
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    assert _ops(_table1_text(one_chip)) == _ops(text)


def _chunk_loop(text: str) -> tuple[list[str], list[str]]:
    """The op lines of the chunk loop's body (the ``while`` nested in the one
    directly under ``popt.round``), and those of every computation it calls
    (its fusions' ops)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{\s*$", line)
        if head:
            name = head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)

    def bodies(lines: list[str], suffix: str = "") -> list[str]:
        return [re.search(r"body=%([^,\s]+)", line).group(1) for line in lines
                if re.match(r"\s*(ROOT )?%while\S* = ", line)
                and re.search(rf'op_name="[^"]*{re.escape(suffix)}"', line)]

    (rnd,) = bodies([ln for lines in comps.values() for ln in lines],
                    obs.ROUND + "/while")
    (chunks,) = bodies(comps[rnd])
    called, todo = [], list(comps[chunks])
    while todo:
        for callee in re.findall(r"(?:calls|to_apply)=%([^,\s}]+)", todo.pop()):
            called += comps[callee]
            todo += comps[callee]
    return comps[chunks], called


def test_table1_chunks_build_only_their_own_rows(one_chip):
    """On the chip's compiler each of Table I's chunks draws random bits and
    gathers donor rows for its own 100 rows alone: no op of the program holds
    the whole population's (800, 1000) bits, and every donor gather yields
    (100, 1000). Every chunk's integer draws are made before the chunk loop,
    whose body keeps few fusions, and each chunk's rows of them are sliced,
    not gathered: the program holds no ``s32`` gather."""
    text = _table1_text(one_chip)
    assert f"u32[{P},{D}]" not in text
    gather = r"= (\w+\[[\d,]*\])\S* gather\("
    gathers = re.findall(gather, text)
    donors = [f"f32[{P // 8},{D}]"] * 3
    assert [g for g in gathers if g.startswith("f32")] == donors, gathers
    assert not [g for g in gathers if g.startswith("s32")], gathers
    body, called = _chunk_loop(text)
    inside = [g for line in body + called for g in re.findall(gather, line)]
    assert inside == donors, inside
    fusions = [line for line in body if re.search(r" fusion\(", line)]
    assert len(fusions) <= 24, len(fusions)


ISLANDS8 = dataclasses.replace(TABLE1, n_islands=8, migration="ring",
                               devices=4, max_evals=8 * P * 20_001)


def test_served_ring_compiles_for_four_chips(topo, monkeypatch):
    """Eight Table I islands over ``devices=4``, the scheduler's sharded
    bucket (``minimize_many`` under ``shard_map``, one job), compiled for the
    described four chips: the ring crosses chips as ``collective-permute``s
    under ``popt.migrate`` in every round, the merge of the history point is
    an all-reduce under ``popt.sync``, exactly one ``while`` sits directly
    under ``popt.round``, and each chunk of each chip's two islands draws
    bits and gathers donors for its own 100 rows alone, and keeps its rows
    of the integer draws without a gather."""
    monkeypatch.setattr(mesh_mod.MeshConfig, "build", lambda self: Mesh(
        np.asarray(topo.devices[: self.devices]), (self.axis,)))
    opt, f = build_optimizer(ISLANDS8), get(ISLANDS8.fn, ISLANDS8.dim)
    _, many, _ = opt._many_fn(f)
    keys = jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=NamedSharding(
        Mesh(np.asarray(topo.devices), ("x",)), PartitionSpec()))
    text = many.lower(keys).compile().as_text()

    def named(pattern: str) -> list[str]:
        return [n for line in text.splitlines() if re.search(pattern, line)
                for n in re.findall(r'op_name="([^"]*)"', line)]

    permutes = named(r" collective-permute-start\(")
    assert permutes and all(
        n.endswith(f"{obs.ROUND}/{obs.MIGRATE}/ppermute") for n in permutes)
    assert any(obs.SYNC in n for n in named(r" all-reduce\("))
    assert len([n for n in named(r"^\s*(ROOT )?%while\S* = ")
                if n.endswith(obs.ROUND + "/while")]) == 1
    assert not [n for n in named(rf"= u32\[1,2,{P},{D}\]") if obs.ROUND in n]
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", text)
    assert gathers.count(f"f32[2,{P // 8},{D}]") == 3, gathers
    assert not [g for g in gathers if g.endswith(f"{P},{D}]")], gathers
    assert f"s32[2,8,{P // 8}]" not in gathers, gathers
    pop_copies = [line for line in _chunk_loop(text)[0]
                  if re.search(rf"= \(f32\[1,2,{P},{D}\].* copy-start\(", line)]
    assert not pop_copies, pop_copies


def test_tile_scores_use_the_attached_tpu_row(monkeypatch):
    """On a TPU the autotuner prices tiles with the chip's own row of the
    peak table; a kind missing from the table is an error, not a default."""
    from repro.parallel import roofline

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(roofline.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(roofline.jax, "devices", lambda: [Chip()])
    assert roofline.device_peaks() == roofline.DEVICE_PEAKS["TPU v5 lite"]
    autotune.predict("de_step", P, D, 8, 1024, tag="rastrigin")
    Chip.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        autotune.predict("de_step", P, D, 8, 1024, tag="rastrigin")
