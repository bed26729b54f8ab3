"""Examples must stay runnable (the reference ships runnable examples as its
de-facto integration suite). ALL nine examples run end-to-end via subprocess
with few-step budgets (round-4 verdict: partial smoke coverage let examples
rot silently).
"""
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent


def _mesh8_env() -> dict:
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    return {"XLA_FLAGS":
            (flags + " --xla_force_host_platform_device_count=8").strip()}


def _run_example(name: str, *args: str, extra_env: dict = None) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, str(_ROOT / "examples" / name), *args],
        capture_output=True, text=True, timeout=420, env=env, cwd=str(_ROOT))
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout


def test_word2vec_example():
    stdout = _run_example("word2vec.py")
    assert "nearest to" in stdout


def test_moe_lm_example():
    stdout = _run_example("moe_lm.py", "--steps", "4")
    assert "load-balance term" in stdout


def test_vae_anomaly_example():
    stdout = _run_example("vae_anomaly.py", "--steps", "8")
    assert "anomalous=" in stdout  # self-asserts anomalies score higher


def test_long_context_sp_example():
    # the 8-device mesh is the point: ppermute/all_to_all must actually run
    stdout = _run_example("long_context_sp.py", extra_env=_mesh8_env())
    assert "mesh: 8 devices" in stdout
    assert "sequence parallelism OK" in stdout
    assert "config+fit sequence parallelism OK" in stdout


def test_moe_lm_expert_parallel_example():
    stdout = _run_example("moe_lm.py", "--steps", "4", "--experts", "8",
                          "--expert-parallel", extra_env=_mesh8_env())
    assert "expert-parallel fit OK over 8 devices" in stdout


def test_lenet_mnist_example():
    stdout = _run_example("lenet_mnist.py", "--epochs", "1", "--batch", "64",
                          "--num-examples", "256")
    assert "Accuracy" in stdout or "accuracy" in stdout


def test_char_rnn_example():
    stdout = _run_example("char_rnn.py", "--steps", "4")
    assert "sample:" in stdout


def test_graph_char_rnn_example():
    stdout = _run_example("graph_char_rnn.py", "--steps", "4")
    assert "generated:" in stdout


def test_parallel_training_example():
    stdout = _run_example("parallel_training.py", extra_env=_mesh8_env())
    assert "DP done" in stdout


def test_tensor_parallel_checkpoint_example():
    stdout = _run_example("tensor_parallel_checkpoint.py",
                          extra_env=_mesh8_env())
    assert "restored W1" in stdout
