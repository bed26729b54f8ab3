"""BENCHMARK.json and the files it names agree; peaks; the device look."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

import run


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_matches_files():
    m = manifest()
    assert m["paths"] == ["benchmark"]
    assert m["command"] == ["python3", "benchmark/run.py"]
    configs = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        cell = run.load_cell(w["name"])
        assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert cell["why"] == w["why"]
        c = configs[w["config"]]
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        assert cell["config"]["source"] == c["source"]
        assert cell["config"]["reduced"] == c["reduced"]
        assert cell["limits"], "a cell with no limit can never be correct"
        kw = cell["config"]["builder"]["kwargs"]
        assert kw["learning_rate"] == cell["config"]["updater"]["learning_rate"]
        assert os.path.exists(os.path.join(
            BENCH, "reference", cell["config"]["reference"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "drivers", cell["traffic"]["driver"] + ".py"))
    e2e = {e["name"] for e in m["end_to_end"]}
    for p in m["per_layer"]:
        with open(os.path.join(BENCH, "metrics", p["name"] + ".json")) as f:
            desc = json.load(f)
        assert {k: p[k] for k in desc} == desc
        assert p["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "metrics", p["name"] + ".py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".json")}
    assert on_disk == {p["name"] for p in m["per_layer"]}


def test_both_cells_load_with_their_metrics():
    for w in manifest()["workloads"]:
        names = [d["name"] for d, _ in run.load_metrics(w["name"])]
        assert len(names) == len(manifest()["per_layer"])


def test_unknown_device_kind_is_an_error():
    assert run.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="not in peaks.json"):
        run.peak_for("TPU v9 imaginary")


def test_cpu_run_exits_nonzero_and_prints_no_result():
    cell = manifest()["workloads"][0]["name"]
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "chip" in r.stderr
