"""BENCHMARK.json against the files it names, and run.py's refusals.

CPU tier of the benchmark (ISSUE 25): every name resolves to its file, names
and units use the allowed characters only, and the command prints no result
without the accelerator or without the program around it.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for group in ("configs", "workloads"):
        seen = [e["name"] for e in bench[group]]
        assert len(set(seen)) == len(seen)
        assert all(NAME.match(n) for n in seen)
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_name_resolves_to_its_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        with open(os.path.join(REPO, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == w["config"] and cfg["reduced"] == entry["reduced"]
        for group in ("runners", "work"):
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", group, cfg[group.rstrip("s")] + ".py"))
        with open(os.path.join(REPO, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["chips"] == w["chips"]
    assert used == set(configs), "a configuration no cell uses"
    for m in bench["end_to_end"]:
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "end_to_end", m["name"] + ".py")), m["name"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]


def test_metrics_follow_the_contract(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells and m["workloads"]
    for cell in cells:      # every cell reports a per-layer metric
        assert any(cell in m["workloads"] for m in bench["per_layer"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_peaks_table_rejects_an_unknown_device():
    sys.path.insert(0, REPO)
    from benchmark import work

    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("cpu")


def _run(argv, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_and_prints_no_result(bench):
    cell = bench["workloads"][0]["name"]
    res = _run(["benchmark/run.py", "--workload", cell, "--seed", "1",
                "--seconds", "1", "--trace", "0"], REPO, JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "needs 1 TPU chip" in res.stderr and "platform='cpu'" in res.stderr
    assert res.stdout.strip() == ""


def test_run_fails_without_the_program_around_it(bench, tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = bench["workloads"][0]["name"]
    # the look for a chip is skipped: even then nothing can be built
    code = ("import sys; sys.argv=['run.py']; import runpy; "
            "m = runpy.run_path('benchmark/run.py'); import jax; "
            "m['run_cell'](%r, 1, 1.0, 0, jax.devices()[:1])" % cell)
    res = _run(["-c", code], str(tmp_path), JAX_PLATFORMS="cpu")
    assert res.returncode != 0 and "mxnet_tpu" in res.stderr
    assert '"correct"' not in res.stdout
    res = _run(["benchmark/run.py", "--workload", cell, "--seed", "1",
                "--seconds", "1", "--trace", "0"], str(tmp_path),
               JAX_PLATFORMS="cpu")
    assert res.returncode != 0 and res.stdout.strip() == ""
