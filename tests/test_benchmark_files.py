"""``BENCHMARK.json`` against the files under ``benchmark/``, in the suite the
driver runs: the manifest-against-files checks of
``benchmark/tests/test_files.py`` (which is outside tier-1), so that an
entry without its files, a file without its entry, a ``workloads`` list that
differs between the two places or names a cell that does not exist, or a name
the contract refuses, fails here.
"""
import importlib.util
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def run():
    """``benchmark/run.py`` as a module, with ``benchmark/`` importable as
    its metric readers expect."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_has_its_files_and_says_the_same(manifest, run):
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"]), w["name"]
        assert cell["why"] == w["why"], w["name"]
        c = configs[w["config"]]
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        assert cell["config"]["source"] == c["source"]
        assert cell["config"]["reduced"] == c["reduced"]
        assert cell["limits"], "a cell with no limit can never be correct"
        kw = cell["config"]["builder"]["kwargs"]
        assert kw["learning_rate"] == (
            cell["config"]["updater"]["learning_rate"])
        for kind, name in (("reference", cell["config"]["reference"]),
                           ("drivers", cell["traffic"]["driver"])):
            assert os.path.exists(os.path.join(BENCH, kind, name + ".py"))
    # and the reverse: no cell or configuration file without its entry, no
    # configuration that no cell runs
    on_disk = lambda d: {f[:-5] for f in os.listdir(os.path.join(BENCH, d))
                         if f.endswith(".json")}
    assert on_disk("workloads") == {w["name"] for w in manifest["workloads"]}
    assert on_disk("configs") == set(configs) == {
        w["config"] for w in manifest["workloads"]}
    assert on_disk("traffic") == {w["traffic"] for w in manifest["workloads"]}


def test_every_metric_has_its_files_and_the_same_workloads(manifest):
    e2e = {e["name"] for e in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for p in manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", p["name"] + ".json")) as f:
            desc = json.load(f)
        assert {k: v for k, v in p.items() if k != "name"} == desc, p["name"]
        assert p["moves"] in e2e
        assert set(p.get("workloads", ())) <= cells, p["name"]
        assert p.get("workloads", True), p["name"]       # a list names a cell
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           p["name"] + ".py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".json")}
    assert on_disk == {p["name"] for p in manifest["per_layer"]}
    for e in manifest["end_to_end"]:
        assert set(e.get("workloads", ())) <= cells, e["name"]


def test_every_cell_loads_the_metrics_that_list_it(manifest, run):
    for w in manifest["workloads"]:
        names = {d["name"] for d, _ in run.load_metrics(w["name"])}
        assert names == {p["name"] for p in manifest["per_layer"]
                         if w["name"] in p.get("workloads", [w["name"]])}
        # every cell reports a per-layer metric that moves each end-to-end
        # metric it reports
        moved = {p["moves"] for p in manifest["per_layer"]
                 if p["name"] in names}
        assert moved == {e["name"] for e in manifest["end_to_end"]}


def test_names_are_ones_the_contract_takes(manifest):
    seen = {}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[kind]:
            assert NAME.match(entry["name"]), (kind, entry["name"])
            assert entry["name"] not in seen.setdefault(kind, set())
            seen[kind].add(entry["name"])
    assert not seen["end_to_end"] & seen["per_layer"]
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(
            c["reduced"]) <= 16
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
