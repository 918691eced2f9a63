"""A later change adds a configuration, a traffic mix and a per-layer metric as
files of their own, and the harness finds them by the names in
``BENCHMARK.json`` without an edit to any file it already has."""
import hashlib
import json
import os
import shutil

import pytest

from bench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark's files, as a checkout holds them."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return str(root)


def test_new_files_are_found_by_name(checkout):
    before = _digest(checkout)
    b = json.load(open(os.path.join(checkout, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(HERE, "data", "tiny_table1.json")))
    cfg["name"] = "added_config"
    with open(os.path.join(checkout, "bench", "configs", "added_config.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(checkout, "bench", "traffic", "added_mix.json"), "w") as fh:
        json.dump({"arrivals": "back_to_back"}, fh)
    with open(os.path.join(checkout, "bench", "metrics", "added.gens_per_s.py"), "w") as fh:
        fh.write("def read(rec):\n    s = rec.get('solve')\n"
                 "    return s['gens_per_s'] if s else None\n")
    b["configs"].append({"name": "added_config", "source": "test",
                         "file": "bench/configs/added_config.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "added.cell", "config": "added_config",
                           "traffic": "added_mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "added.gens_per_s", "unit": "gens/s",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "evals_per_s",
                           "workloads": ["added.cell"]})
    b["end_to_end"][0]["workloads"].append("added.cell")
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as fh:
        json.dump(b, fh)
    after = _digest(checkout)
    assert all(after[k] == v for k, v in before.items())   # nothing edited

    cell = harness.Cell(checkout, "added.cell")
    assert cell.config["name"] == "added_config"
    assert [m["name"] for m in cell.per_layer()] == ["added.gens_per_s"]
    assert {m["name"] for m in cell.end_to_end()} == {"evals_per_s", "setup_s"}

    r = harness.run(checkout, "added.cell", 5, 0.5, False, require_chip=False)
    assert r["correct"] and set(r["metrics"]) == {"evals_per_s", "setup_s"}
    r = harness.run(checkout, "added.cell", 6, 0.5, True, require_chip=False)
    assert r["correct"] and r["metrics"]["added.gens_per_s"]["value"] > 0
    assert "device_ops" in r["breakdown"] and r["device"]["window_s"] > 0


def test_off_the_chip_a_run_fails_before_measuring():
    with pytest.raises(harness.NoChip):
        harness.run(ROOT, "table1.de_chunked", 1, 1.0, False)


def test_every_cell_of_the_benchmark_has_its_files():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in b["workloads"]:
        cell = harness.Cell(ROOT, w["name"])
        assert os.path.exists(os.path.join(BENCH, "drivers", cell.config["driver"] + ".py"))
        assert cell.end_to_end() and cell.per_layer()
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]))


def test_the_command_off_the_chip_prints_no_result():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "table1.de_chunked", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr
