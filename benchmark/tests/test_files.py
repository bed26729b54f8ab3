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
    cells = {w["name"] for w in m["workloads"]}
    for p in m["per_layer"]:
        with open(os.path.join(BENCH, "metrics", p["name"] + ".json")) as f:
            desc = json.load(f)
        # the same in both places, the `workloads` list too
        assert {k: v for k, v in p.items() if k != "name"} == desc
        assert p["moves"] in e2e
        assert set(p.get("workloads", ())) <= cells, p["name"]
        assert os.path.exists(os.path.join(BENCH, "metrics", p["name"] + ".py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".json")}
    assert on_disk == {p["name"] for p in m["per_layer"]}


def test_every_cell_loads_the_metrics_that_list_it():
    m = manifest()
    for w in m["workloads"]:
        names = {d["name"] for d, _ in run.load_metrics(w["name"])}
        assert names == {p["name"] for p in m["per_layer"]
                         if w["name"] in p.get("workloads", [w["name"]])}
        # the contract: every cell reports at least one per-layer metric
        # that moves each end-to-end metric it reports
        moved = {p["moves"] for p in m["per_layer"] if p["name"] in names}
        assert moved == {e["name"] for e in m["end_to_end"]}


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
