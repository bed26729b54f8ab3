"""The rest of a run, driven on the CPU at a tiny size (``drive.py`` skips
the look for a chip): a throw-away configuration, cell and metric added as
files only are found and run; the result line has the contract's keys; the
lower-precision control and each fault the cell can have come out as not
correct, through the same comparison and limits as a sound run."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LIMITS = json.load(open(os.path.join(HERE, "data", "tiny_limits.json")))


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """A copy of benchmark/ with the throw-away files added and none edited."""
    root = tmp_path_factory.mktemp("checkout")
    dst = root / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(dst) for p in fs}
    extra = os.path.join(HERE, "data", "extra")
    for d, _, fs in os.walk(extra):
        for f in fs:
            rel = os.path.relpath(os.path.join(d, f), extra)
            assert not (dst / rel).exists(), "an added file replaced one"
            shutil.copy(os.path.join(d, f), dst / rel)
    for cell, limits in LIMITS.items():
        cfg = cell.rsplit("-", 1)[0]
        (dst / "workloads" / f"{cell}.json").write_text(json.dumps({
            "config": cfg, "traffic": "tiny-b16", "chips": 1,
            "why": "throw-away cell of the tests", "limits": limits}))
    return str(dst), before


def drive(bench, cell, seed, trace=0, fault=None, seconds=1.0):
    cmd = [sys.executable, os.path.join(HERE, "drive.py"), bench, cell,
           str(seed), str(seconds), str(trace)] + ([fault] if fault else [])
    r = subprocess.run(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=os.path.dirname(bench), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.parametrize("cell", sorted(LIMITS))
def test_throwaway_cell_runs_and_is_correct(bench_copy, cell):
    bench, _ = bench_copy
    out, err = drive(bench, cell, 2147483659)
    assert KEYS <= set(out) and list(out)[-1] == "compared"
    assert out["correct"] is True, err[-2000:]
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert "compiles inside the window: 0 backend" in err
    # each number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(out["compared"]) - 1:]
    assert tail[-1].endswith("correct: True")
    assert all("compared" in l and "limit" in l for l in tail[:-1])
    # the cache stays inside the checkout
    assert os.path.isdir(os.path.join(os.path.dirname(bench), ".jax_cache"))


def test_traced_run_reports_per_layer_metrics_and_the_added_one(bench_copy):
    bench, before = bench_copy
    out, _ = drive(bench, "tiny-resnet-train", 7, trace=1)
    assert KEYS | {"breakdown"} <= set(out)
    assert {"busy_s", "window_s"} <= set(out["device"])
    m = out["metrics"]
    assert m["extra.steps_per_dispatch"]["value"] == 4
    assert "train_samples_per_s" not in m and "step.mfu" in m
    assert 0 < m["device.idle_pct"]["value"] < 100
    assert len(out["breakdown"]["device_ops"]) <= 10
    # the metric's `workloads` key keeps it out of the other cell
    out2, _ = drive(bench, "tiny-vgg-train", 7, trace=1)
    assert "extra.steps_per_dispatch" not in out2["metrics"]
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, fs in os.walk(bench) for p in fs if p in before}
    assert after == before, "running edited a file of the benchmark"


@pytest.mark.parametrize("cell", sorted(LIMITS))
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_broken_timed_path_is_not_correct(bench_copy, cell, fault):
    bench, _ = bench_copy
    out, err = drive(bench, cell, 2147483659, fault=fault)
    assert out["correct"] is False, err[-2000:]
    assert any(r["limit"] is not None and not r["value"] <= r["limit"]
               for r in out["compared"].values())
