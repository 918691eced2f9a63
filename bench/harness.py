"""What every cell's run shares: finding the cell's files by name, the chip
check, the compile cache, the check against the reference, the per-layer
readers and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable

from bench import check, peaks, traffic


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (metric and driver names hold
    dots, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell of ``BENCHMARK.json`` with its configuration and mix loaded."""

    def __init__(self, root: str, workload: str, bench: dict | None = None) -> None:
        self.root = root
        self.bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
        self.workload = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.mix = load_json(os.path.join(root, "bench", "traffic",
                                          self.workload["traffic"] + ".json"))
        traffic.validate(self.mix)
        self.chips = int(self.workload["chips"])

    @property
    def name(self) -> str:
        return self.workload["name"]

    def driver(self):
        d = self.config["driver"]
        return load_module(os.path.join(self.root, "bench", "drivers", d + ".py"),
                           f"bench_driver_{d}")

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str) -> Callable[[dict], Any]:
        path = os.path.join(self.root, "bench", "metrics", metric + ".py")
        return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU: device 0 is {devs[0].platform!r}")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def open_chip(root: str, cell: Cell,
              require_chip: bool = True) -> tuple[dict, str | None]:
    """The chip check, the program on the path and its compile cache:
    ``(device info, cache directory)``."""
    info = device_info(cell.chips, require_chip)
    if os.path.join(root, "src") not in sys.path:
        sys.path.insert(0, os.path.join(root, "src"))
    return info, use_compile_cache() if require_chip else None


def use_compile_cache() -> str | None:
    """JAX's persistent cache through the program's own switch (the fixed
    ``<checkout>/.jax_cache``, or where ``JAX_COMPILATION_CACHE_DIR`` says),
    with every program cached however short its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def memory_peak(n: int) -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float | None = None, require_chip: bool = True,
        bench: dict | None = None) -> dict:
    """Set up, warm, measure and check one cell; returns the result object,
    with the lines meant for standard error under ``"_stderr"``."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, workload, bench)
    info, cache = open_chip(root, cell, require_chip)
    ctx = {"cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
           "root": root, "t_start": t_start, "info": info}
    out = cell.driver().run(ctx)
    info["memory_peak_bytes"] = memory_peak(cell.chips)
    log = [f"device: {json.dumps(info)}", f"compile cache: {cache}"]
    log += out["log"]
    verdict = check.check(cell.config, out["answers"], seed)
    if trace:
        rec = dict(out["record"], driver=cell.config["driver"], trace=out["trace"],
                   peak=peaks.peaks(info["kind"]) if require_chip else None)
        metrics = {}
        for m in cell.per_layer():
            v = cell.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        t = out["trace"]
        log += [f"trace loop {n}: {r!r} runs; {txt}" for n, r, txt in t["loops"]]
        info["busy_s"], info["window_s"] = t["busy_s"], t["window_s"]
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    result = {"correct": verdict["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": info}
    if trace:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    result["checks"] = verdict["numbers"]
    result["_stderr"] = log + verdict["lines"]      # the checks come last
    return result
