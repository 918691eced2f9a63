"""Drive the optimizer's main path once on a TPU and check what comes out.

    PYTHONPATH=src python chip_smoke.py              # one chip
    PYTHONPATH=src python chip_smoke.py --chips 4    # island sharding only

One chip runs three phases, all at the paper's Table I width (1000-D,
population 800):

  kernels   the five fused Pallas kernels, tiled by the autotuner, against
            their pure-jnp references in ``kernels/ref.py``; every compiled
            program must hold a Mosaic kernel (``tpu_custom_call``);
  table1    the Table I workload (CEC'2008 shifted Rosenbrock, w=0.5,
            px=0.2, chunked DE) through ``IslandOptimizer.minimize`` for a
            few hundred generations: xla, pallas and fused, each pair
            checked for fixed-seed parity over the first generations;
  served    the ``opt_serve`` service (two pool workers) answering 16 jobs
            of four shape-classes through its JSONL ops, each job checked
            against a standalone ``minimize`` of the same request.

``--chips 4`` runs only an island-sharded request (``devices=4``) and the
same request on one device.

The script exits non-zero on any fault, and before anything else when JAX
finds no TPU. Everything it prints goes to earlier lines; the last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RTOL = ATOL = 1e-4      # the fixed-seed parity tolerances of tests/test_backends.py
TABLE1_GENS = 200       # of the paper's 20,000
PARITY_GENS = 3         # generations over which each parity pair must agree
SEEDS = (0, 1, 2, 3)    # served jobs per shape-class


class SmokeFailure(AssertionError):
    """A phase produced a wrong, missing or non-finite result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    """The attached accelerator as JAX reports it; fails off the TPU."""
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU: device 0 is {devs[0].platform!r}")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel_err(a, b) -> float:
    """Largest ``|a - b| / (|b| + 1)`` over the leaves of two outputs."""
    errs = [float(jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                                  - jnp.asarray(y, jnp.float32))
                          / (jnp.abs(jnp.asarray(y, jnp.float32)) + 1.0)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return max(errs)


@contextlib.contextmanager
def mosaic_programs():
    """Yields a list that, after the block, names every program compiled
    inside it that holds a Mosaic kernel (an interpreted kernel holds none)."""
    found: list[str] = []
    prev = jax.config.values["jax_dump_ir_to"]
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield found
        finally:
            jax.config.update("jax_dump_ir_to", prev)
            for p in sorted(glob.glob(os.path.join(d, "*.mlir"))):
                with open(p) as fh:
                    if "tpu_custom_call" in fh.read():
                        found.append(os.path.basename(p))


# -- kernels -----------------------------------------------------------------

KERNELS = ("bench_eval", "de_step", "pso_step", "ga_step", "eval_select")


def kernel_case(kind: str, f, P: int, D: int, key):
    """(kernel entry, its kernels/ref.py reference, arguments) for one
    kernel on objective ``f`` — inputs drawn from ``key`` as the parity
    tests in tests/test_kernels.py draw them."""
    from repro.kernels import ref
    from repro.kernels import registry as kreg
    from repro.kernels.bench_eval import bench_eval
    from repro.kernels.de_step import de_step
    from repro.kernels.eval_select import eval_select
    from repro.kernels.ga_step import ga_step
    from repro.kernels.pso_step import pso_step

    tag = kreg.get_spec(f.name).eval_tag
    kw = dict(fn=tag, shift=f.shift, bias=f.bias)
    ks = jax.random.split(key, 8)
    lo, hi = max(f.lo, -5.0), min(f.hi, 5.0)
    box = [jax.random.uniform(k, (P, D), minval=lo, maxval=hi) for k in ks[:3]]
    fit = [ref.bench_eval_ref(x, tag, f.shift, f.bias) for x in box]
    u = jax.random.uniform(ks[3], (P, D))
    if kind == "bench_eval":
        return (lambda x: bench_eval(x, tag, shift=f.shift, bias=f.bias),
                lambda x: ref.bench_eval_ref(x, tag, f.shift, f.bias),
                (box[0],))
    if kind == "de_step":
        i = jnp.arange(P)
        idx = jnp.stack([(i + 3) % P, (i + 7) % P, (i + 11) % P])
        jr = jax.random.randint(ks[4], (P,), 0, D)
        return (lambda *a: de_step(*a, **kw),
                lambda *a: ref.de_step_ref(*a, **kw),
                (box[0], fit[0], idx, u, jr))
    if kind == "pso_step":
        v = 0.1 * jax.random.normal(ks[4], (P, D))
        r2 = jax.random.uniform(ks[5], (P, D))
        gbest = box[1][jnp.argmin(fit[1])]
        return (lambda *a: pso_step(*a, vmax=2.0, **kw),
                lambda *a: ref.pso_step_ref(*a, vmax=2.0, **kw),
                (box[0], v, box[1], fit[1], u, r2, gbest))
    if kind == "ga_step":
        cut = jax.random.randint(ks[4], (P,), 1, D)
        co = jax.random.uniform(ks[5], (P,))
        nz = jax.random.normal(ks[6], (P, D))
        return (lambda *a: ga_step(*a, **kw),
                lambda *a: ref.ga_step_ref(*a, **kw),
                (box[0], box[1], box[2], fit[2], cut, co, u, nz))
    if kind == "eval_select":
        th = 2.0 * jax.random.uniform(ks[4], (P,))
        return (lambda *a: eval_select(*a, **kw),
                lambda *a: ref.eval_select_ref(*a, **kw),
                (box[0], fit[0], box[1], th))
    raise KeyError(kind)


def kernels_phase(P: int, D: int) -> None:
    """Each kernel x objective: compile at the autotuner's tile, require a
    Mosaic kernel in the compiled program, run, compare with the reference
    (bool selection masks exactly, values within RTOL)."""
    from repro.functions import get
    from repro.kernels import registry as kreg

    names = ["shifted_rosenbrock", "rastrigin"]
    if kreg.supported("griewank"):
        names.append("griewank")
    for kind in KERNELS:
        for name in names:
            f = get(name, D)
            entry, reference, args = kernel_case(kind, f, P, D,
                                                 jax.random.PRNGKey(7))
            compiled = jax.jit(entry).lower(*args).compile()
            out = jax.block_until_ready(compiled(*args))
            check("tpu_custom_call" in compiled.as_text(),
                  f"{kind}/{name}: no Mosaic kernel in the compiled program")
            expect = reference(*args)
            outs, exps = jax.tree.leaves(out), jax.tree.leaves(expect)
            check(len(outs) == len(exps), f"{kind}/{name}: output arity")
            for o, e in zip(outs, exps):
                check(o.shape == e.shape, f"{kind}/{name}: shape {o.shape} "
                      f"!= {e.shape}")
                if o.dtype == jnp.bool_:
                    n_bad = int(jnp.sum(o != e))
                    check(n_bad == 0,
                          f"{kind}/{name}: {n_bad} selection decisions differ")
                else:
                    check(bool(jnp.all(jnp.isfinite(o) | ~jnp.isfinite(e))),
                          f"{kind}/{name}: non-finite output")
            err = rel_err([o for o in outs if o.dtype != jnp.bool_],
                          [e for e in exps if e.dtype != jnp.bool_])
            log(f"kernels: {kind:11s} {name:18s} {P}x{D} max_rel_err={err!r}")
            check(err <= RTOL, f"{kind}/{name}: max rel err {err} > {RTOL}")


# -- Table I -----------------------------------------------------------------

def table1_phase(P: int, D: int, gens: int) -> None:
    """The Table I workload through ``IslandOptimizer.minimize``: chunked DE
    on xla and pallas, DE with the fused ``de_step`` kernel, and the xla
    sync-barrier run the fused step replaces. Every run must improve on the
    initial best; each parity pair must agree over the first generations."""
    from repro.configs.popt_bench import CONFIG
    from repro.core import ALGORITHMS, ExecutorConfig, IslandConfig, IslandOptimizer
    from repro.core.islands import uniform_init
    from repro.functions import get

    f = get(CONFIG.function, D)
    key = jax.random.PRNGKey(0)
    # one generation per round, so the history is per generation
    cfg = IslandConfig(n_islands=1, pop=P, dim=D, migration="none",
                       sync_every=1, max_evals=P * (gens + 1))
    base = dict(w=CONFIG.w, px=CONFIG.px, strategy=CONFIG.strategy)
    runs = {
        "xla_chunked": ("xla", dict(barrier_mode=CONFIG.barrier_mode)),
        "pallas_chunked": ("pallas", dict(barrier_mode=CONFIG.barrier_mode)),
        "xla_sync": ("xla", dict(barrier_mode="sync")),
        "fused": ("xla", dict(barrier_mode=CONFIG.barrier_mode, fused=True)),
    }
    # minimize splits off the init key first; its population's best is the
    # bar every run has to beat
    _, ik = jax.random.split(key)
    init_best = float(jnp.min(f.eval_population(
        uniform_init(ik, P, D, f.lo, f.hi))))
    log(f"table1: {CONFIG.function} {D}-D pop {P}, {gens} generations, "
        f"initial best {init_best!r}")
    res = {}
    for name, (backend, extra) in runs.items():
        opt = IslandOptimizer(ALGORITHMS["de"], cfg, params={**base, **extra},
                              exec_cfg=ExecutorConfig(backend=backend))
        with mosaic_programs() as mosaic:
            r = opt.minimize(f, key)
        again = opt.minimize(f, key)
        log(f"table1: {name:15s} value={r.value!r} gens={r.n_gens} "
            f"evals={r.n_evals} mosaic_programs={len(mosaic)}")
        check(np.isfinite(r.value), f"table1/{name}: value {r.value}")
        check(r.value < init_best,
              f"table1/{name}: {r.value} does not improve on {init_best}")
        check(again.value == r.value, f"table1/{name}: a rerun on the same "
              f"seed gave {again.value}, not {r.value}")
        check((len(mosaic) > 0) == (backend == "pallas" or "fused" in extra),
              f"table1/{name}: {len(mosaic)} programs hold a Mosaic kernel")
        res[name] = r
    for a, b in (("pallas_chunked", "xla_chunked"), ("fused", "xla_sync")):
        ha = np.asarray(res[a].history[:PARITY_GENS])
        hb = np.asarray(res[b].history[:PARITY_GENS])
        close = np.allclose(ha, hb, rtol=RTOL, atol=ATOL)
        log(f"table1: parity {a} vs {b}: first {PARITY_GENS} generations "
            f"max_abs_diff={float(np.max(np.abs(ha - hb)))!r} "
            f"within_tol={close}; final values differ by "
            f"{abs(res[a].value - res[b].value)!r}")
        check(close, f"table1: {a} and {b} part within {PARITY_GENS} "
              f"generations: {ha} vs {hb}")


# -- served path -------------------------------------------------------------

def served_classes(dim: int, pop: int) -> dict[str, dict]:
    """Four shape-classes at Table I width, as JSON requests (seed added
    per job)."""
    common = dict(dim=dim, pop=pop, sync_every=10)
    return {
        "de_xla": dict(fn="shifted_rosenbrock", algo="de", backend="xla",
                       n_islands=2, max_evals=2 * pop * 51, **common),
        "pso_fused_pallas": dict(fn="rastrigin", algo="pso",
                                 backend="pallas", params={"fused": True},
                                 n_islands=2, max_evals=2 * pop * 51,
                                 **common),
        "portfolio": dict(fn="rastrigin", portfolio=["de", "pso", "sa"],
                          n_islands=3, max_evals=3 * pop * 51, **common),
        "asd_hybrid": dict(fn="shifted_rosenbrock", algo="de", n_islands=2,
                           polish="asd", polish_every=2, polish_topk=2,
                           polish_steps=2,
                           # 6 rounds and 3 polish events of 2 islands x 2
                           # points x 2 ASD steps of 4*dim + 8 evaluations
                           max_evals=2 * pop * 61 + 24 * (4 * dim + 8),
                           **common),
    }


def serve_jobs(service, classes: dict[str, dict]) -> dict:
    """Submit every (class, seed) job through the JSONL ops, poll, read the
    bucket status, fetch every result. Returns {(class, seed): (reply,
    OptResponse)}; the response object keeps the per-round history."""
    ids = {}
    for cls, req in classes.items():
        for seed in SEEDS:
            reply = service.handle({"op": "submit",
                                    "request": dict(req, seed=seed)})
            check("error" not in reply, f"served/{cls}: submit {reply}")
            ids[cls, seed] = reply["id"]
    for key, jid in ids.items():
        reply = service.handle({"op": "poll", "id": jid})
        check(reply.get("status") in ("queued", "running", "done"),
              f"served/{key}: poll {reply}")
    status = service.handle({"op": "status"})
    check("buckets" in status and len(status["buckets"]) == len(classes),
          f"served: status {status}")
    out = {}
    for key, jid in ids.items():
        resp = service.scheduler.poll(jid)
        reply = service.handle({"op": "result", "id": jid})
        check(reply.get("status") == "done", f"served/{key}: {reply}")
        check(np.isfinite(reply["value"]), f"served/{key}: {reply['value']}")
        out[key] = (reply, resp)
    return out


def compare_to_standalone(cls: str, req: dict, served: dict) -> None:
    """Each served job against ``minimize`` of the same request: print
    whether the two are bit-identical, else the largest difference of the
    final values and the first round at which the histories part."""
    from repro.core.api import OptRequest
    from repro.core.scheduler import build_optimizer
    from repro.functions import get

    r0 = OptRequest.from_dict(dict(req, seed=SEEDS[0]))
    opt = build_optimizer(r0)
    f = get(r0.fn, r0.dim)
    n_same, max_diff, first_part = 0, 0.0, None
    for seed in SEEDS:
        ref = opt.minimize(f, jax.random.PRNGKey(seed))
        reply, resp = served[cls, seed]
        hs = np.asarray(resp.result.history)
        hr = np.asarray(ref.history)
        same = reply["value"] == ref.value and np.array_equal(hs, hr)
        n_same += same
        max_diff = max(max_diff, abs(reply["value"] - ref.value))
        if not same and hs.shape == hr.shape:
            part = int(np.argmax(hs != hr)) if np.any(hs != hr) else len(hs)
            first_part = part if first_part is None else min(first_part, part)
    log(f"served: {cls:17s} bit_identical={n_same}/{len(SEEDS)} "
        f"max_final_diff={max_diff!r} first_round_parted={first_part}")


def served_phase(dim: int, pop: int) -> None:
    """The opt_serve service in process, two pool workers, 16 jobs."""
    from repro.launch.opt_serve import OptimizationService

    classes = served_classes(dim, pop)
    service = OptimizationService(workers=2, max_batch=len(SEEDS),
                                  flush_ms=50.0)
    try:
        with mosaic_programs() as mosaic:
            served = serve_jobs(service, classes)
        again = serve_jobs(service, classes)
        check(service.handle({"op": "quit"}) == {"bye": True}, "served: quit")
    finally:
        service.scheduler.close()
    for key, (reply, _) in served.items():
        check(again[key][0]["value"] == reply["value"],
              f"served/{key}: a second pass gave a different value")
    log(f"served: {len(served)} jobs, {len(classes)} shape-classes, "
        f"dim {dim} pop {pop}, mosaic_programs={len(mosaic)}")
    check(len(mosaic) > 0, "served: no Mosaic kernel in the fused PSO bucket")
    for cls, req in classes.items():
        vals = [served[cls, s][0]["value"] for s in SEEDS]
        log(f"served: {cls:17s} gens={served[cls, SEEDS[0]][0]['n_gens']} "
            f"values={vals!r}")
    for cls, req in classes.items():
        compare_to_standalone(cls, req, served)


# -- four chips ----------------------------------------------------------------

def sharded_phase(dim: int, pop: int, devices: int) -> None:
    """OptRequest(devices=4, n_islands=8) against the same request at
    devices=1, through the service; the sharded state's island axis must
    span the devices."""
    from repro.core.api import OptRequest
    from repro.core.scheduler import build_optimizer
    from repro.functions import get
    from repro.launch.opt_serve import OptimizationService

    base = dict(fn="shifted_rosenbrock", algo="de", dim=dim, pop=pop,
                n_islands=8, migration="ring", sync_every=10,
                max_evals=8 * pop * 101, seed=0)
    service = OptimizationService(workers=2, max_batch=1, flush_ms=50.0)
    replies = {}
    try:
        for n in (devices, 1):
            sub = service.handle({"op": "submit",
                                  "request": dict(base, devices=n)})
            check("error" not in sub, f"sharded/devices={n}: {sub}")
            replies[n] = service.handle({"op": "result", "id": sub["id"]})
            r = replies[n]
            check(r.get("status") == "done", f"sharded/devices={n}: {r}")
            check(np.isfinite(r["value"]), f"sharded/devices={n}: {r}")
            log(f"sharded: devices={n} value={r['value']!r} "
                f"gens={r['n_gens']} evals={r['n_evals']}")
        service.handle({"op": "quit"})
    finally:
        service.scheduler.close()
    a, b = replies[devices], replies[1]
    same = a["value"] == b["value"] and a["arg"] == b["arg"]
    log(f"sharded: devices={devices} vs devices=1 bit_identical={same} "
        f"final_diff={abs(a['value'] - b['value'])!r} max_arg_diff="
        f"{float(np.max(np.abs(np.subtract(a['arg'], b['arg']))))!r}")

    req = OptRequest.from_dict(dict(base, devices=devices))
    opt = build_optimizer(req)
    f = get(req.fn, req.dim)
    state = opt._shard_state(opt._init_state(opt._build(f),
                                             jax.random.PRNGKey(0)))
    for name, leaf in state.items():
        n_dev = len(leaf.sharding.device_set)
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        check(n_dev == devices and rows == {req.n_islands // devices},
              f"sharded: state[{name!r}] spans {n_dev} devices with "
              f"{rows} island rows each")
    log(f"sharded: every state leaf's island axis spans {devices} devices, "
        f"{req.n_islands // devices} islands each")
    for d in jax.devices()[:devices]:
        stats = d.memory_stats() or {}
        log(f"sharded: {d} memory_stats bytes_in_use="
            f"{stats.get('bytes_in_use')} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')} bytes_limit="
            f"{stats.get('bytes_limit')}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the island-sharded request on 4 chips")
    args = ap.parse_args(argv)

    info = device_info(args.chips)
    log(f"device: {json.dumps(info)}")

    from repro.configs.popt_bench import CONFIG
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        sharded_phase(CONFIG.dim, CONFIG.pop, devices=4)
    else:
        kernels_phase(CONFIG.pop, CONFIG.dim)
        table1_phase(CONFIG.pop, CONFIG.dim, TABLE1_GENS)
        served_phase(CONFIG.dim, CONFIG.pop)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
