"""chip_smoke.py's control flow, rehearsed on the CPU at toy sizes.

The script is the proof that the system starts on the chip; what can be
checked without one is that every phase runs, prints its line, and that the
run ENDS in failure here: a CPU run is never a pass. Both rehearsals run the
script the way the driver does, as a subprocess from the checkout's root.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ONE_CHIP = ["native", "resnet50_train", "resnet50_serve", "xent", "flash",
            "transformer_train", "lstm", "char_rnn_train", "decode"]
FOUR_CHIPS = ["dp_train", "replica_set"]


def _run(args, devices, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    return proc, lines


@pytest.mark.parametrize("args,devices,phases", [
    (["--tiny"], 1, ONE_CHIP),
    (["--tiny", "--chips", "4"], 4, FOUR_CHIPS),
], ids=["one-chip", "four-chips"])
def test_tiny_rehearsal_runs_every_phase_and_fails_on_cpu(
        args, devices, phases, tmp_path):
    proc, lines = _run(args, devices, tmp_path)
    assert proc.returncode == 1, proc.stderr[-2000:]
    last = lines[-1]
    assert last == {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                            "count": devices}}
    ran = [l["phase"] for l in lines[:-1]
           if l.get("phase") not in ("start", "caches")]
    assert ran == phases            # this option's phases, and no other
    by = {l["phase"]: l for l in lines[:-1]}
    for name in phases:
        assert "seconds" in by[name] and "compile_seconds" in by[name]
        assert "error" not in by[name], by[name]
    if devices == 1:
        # what has an interpret-mode hook engages in the rehearsal; the
        # flash kernel inside a layer has none, and that alone fails the run
        assert by["lstm"]["kernels"]["lstm_cell"]["engaged"] >= 1
        assert by["decode"]["kernels"]["paged_gather"]["engaged"] >= 1
        assert by["decode"]["kernels"]["int8_matmul"]["engaged"] >= 1
        assert by["decode"]["tokens_equal_dense_engine"] is True
        assert by["transformer_train"]["not_engaged"] == [
            "flash_attention", "flash_attention_bwd"]
        assert [n for n in phases if not by[n]["ok"]] == ["transformer_train"]
    else:
        assert all(by[n]["ok"] for n in phases)
        assert by["replica_set"]["four_distinct_devices"] is True
        assert by["dp_train"]["per_device_batch"] == 2
        assert by["dp_train"]["all_reduce_ops"] >= 1
    # everything the run cached sits under the one directory it was given
    assert by["caches"]["root"] == str(tmp_path / "cache")


def test_no_accelerator_runs_nothing_and_fails(tmp_path):
    proc, lines = _run([], 1, tmp_path)
    assert proc.returncode == 1
    assert [l.get("phase") for l in lines[:-1]] == ["start", "caches"]
    assert lines[-1]["ok"] is False
