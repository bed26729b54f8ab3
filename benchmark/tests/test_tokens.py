"""The language-model cell's files: the configuration against the catalog's
row, the operations its reference lists, its metrics, and the token driver
with the reference's Adam follower driven on the CPU at a tiny size
(``drive.py`` skips the look for a chip); each planted fault comes out as
not correct through the token driver and through the data-parallel one
(``drive_faults.py``)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE
from test_harness import KEYS, drive

import flops
import run

CELL = "deepseek-v2-lite-ep8-train-seq4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"step.attention_ms", "step.moe_ms", "kernel.grouped_matmul_roofline",
       "kernel.mla_core_roofline", "moe.expert_load_max_over_mean",
       "moe.padded_rows_pct"}
# what both language-model cells list besides (PR 34): the dense products'
# roofline, and five of NEW, whose readers know no model
BOTH_LM = (NEW - {"kernel.mla_core_roofline"}) | {"kernel.dense_roofline"}
UNLISTED = {"fit.staging_wait_pct", "fit.dispatch_ms_p50",
            "input.stage_ms_per_batch", "step.mfu", "step.device_ms",
            "device.idle_pct", "device.peak_hbm_gb", "compile.cache_load_s"}
TINY_LIMITS = {"loss_gap": 1e-4, "velocity_gap": 1e-3, "change_gap": 1e-3,
               "velocity_gap_median": 1e-4, "change_gap_median": 1e-4}


def test_configuration_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    cell = run.load_cell(CELL)
    cfg = cell["config"]
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "DeepSeek-V2-Lite")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    kw = cfg["builder"]["kwargs"]
    assert (kw["n_layers"], kw["experts_held"], kw["vocab_rows"]) == (
        cfg["num_hidden_layers"], [0, cfg["n_routed_experts"]],
        cfg["vocab_size"])
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("n_router_outputs", "n_routed_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("n_shared_experts", "n_shared_experts"),
                         ("first_k_dense", "first_k_dense_replace"),
                         ("rope_scaling", "rope_scaling")):
        assert kw[ours] == row["config"][theirs], ours
    assert kw["seq_len"] == cell["traffic"]["seq_len"] == 4096
    assert cell["traffic"]["batch"] * kw["seq_len"] == 16384


def test_reference_lists_the_operations_the_issue_counted():
    from reference import deepseek_v2_lite as ref

    cfg = run.load_cell(CELL)["config"]
    kw = cfg["builder"]["kwargs"]
    need = flops.train_flops_of(cfg)
    assert round(need / kw["seq_len"] / 1e9, 2) == 2.15     # GFLOP a token
    share = {}
    for l in ref.layers(kw):
        kind = l["name"].split("/")[1]
        share[kind] = share.get(kind, 0) + 6 * l["nin"] * l["nout"] / need
    assert round(100 * share["routed"]) == 9
    assert round(100 * share["shared"]) == 24
    assert round(100 * share["core"]) == 18
    assert round(100 * share["ffn"]) == 19
    assert round(100 * share["W"]) == 7
    assert round(100 * sum(share[k] for k in ("Wq", "Wkva", "Wkvb",
                                              "Wo"))) == 23
    shapes = ref._shapes(ref._cfg(kw))
    n = sum(int(__import__("math").prod(s)) for s in shapes.values())
    assert round(n / 1e6, 1) == 635.5
    assert round(16 * n / 1e9, 2) == 10.17                  # GB with Adam


def test_the_cell_reads_its_listed_metrics_and_the_unlisted_eight():
    names = {d["name"] for d, _ in run.load_metrics(CELL)}
    assert names == NEW | BOTH_LM | UNLISTED
    for other in ("resnet50-train-b128", "vgg16-train-b128"):
        assert not (NEW | BOTH_LM) & {
            d["name"] for d, _ in run.load_metrics(other)}


@pytest.fixture(scope="module")
def tokens_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny language-model cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "tokens"), dst,
                    dirs_exist_ok=True)
    (dst / "workloads" / "tiny-dsv2-train.json").write_text(json.dumps({
        "config": "tiny-dsv2", "traffic": "tiny-seq16-b2", "chips": 1,
        "why": "throw-away cell of the tests", "limits": TINY_LIMITS}))
    for name in NEW:    # the metrics' `workloads` must list the tiny cell
        path = dst / "metrics" / f"{name}.json"
        desc = json.loads(path.read_text())
        desc["workloads"].append("tiny-dsv2-train")
        path.write_text(json.dumps(desc))
    return str(dst)


def test_token_cell_runs_and_is_correct_on_the_cpu(tokens_copy):
    out, err = drive(tokens_copy, "tiny-dsv2-train", 2147483659)
    assert KEYS <= set(out) and out["correct"] is True, err[-2000:]
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert "compiles inside the window: 0 backend" in err
    # the program's routed-rows counter and the reference's count agree
    line = next(l for l in err.splitlines() if "rows routed" in l)
    prog, ref = line.split("program ")[1].split(", reference ")
    assert [n for _, n in eval(prog)] == eval(ref)


def test_traced_token_cell_reads_counters_and_leaves_silent_scopes_out(
        tokens_copy):
    """The canned device trace holds none of the program's scopes: the
    four trace metrics read nothing and raise nothing; the two counter
    metrics read the expert layers' rows."""
    out, _ = drive(tokens_copy, "tiny-dsv2-train", 7, trace=1)
    m = out["metrics"]
    assert m["moe.padded_rows_pct"]["value"] == 0.0     # no kernel on a CPU
    assert m["moe.expert_load_max_over_mean"]["value"] >= 1.0
    assert not {"step.attention_ms", "step.moe_ms",
                "kernel.grouped_matmul_roofline",
                "kernel.mla_core_roofline"} & set(m)


def drive_fault(bench, cell, fault, devices=1):
    """One run with ``fault`` planted under the cell's own driver."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_faults.py"), bench, cell,
         "2147483659", "0.5", fault],
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={devices}")),
        cwd=os.path.dirname(bench), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


def assert_not_correct(out, err):
    assert out["correct"] is False, err[-2000:]
    assert any(r["limit"] is not None and not r["value"] <= r["limit"]
               for r in out["compared"].values())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_broken_token_path_is_not_correct(tokens_copy, fault):
    """The faults ``test_harness`` plants under ``train_fit``, under the
    token driver: the readings are Adam's ``m`` and the int32 pool's."""
    assert_not_correct(*drive_fault(tokens_copy, "tiny-dsv2-train", fault))


@pytest.fixture(scope="module")
def dp4_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny data-parallel cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for extra in ("extra", "dp4"):
        shutil.copytree(os.path.join(HERE, "data", extra), dst,
                        dirs_exist_ok=True)
    limits = json.load(open(os.path.join(HERE, "data", "tiny_limits.json")))
    (dst / "workloads" / "tiny-resnet-dp4.json").write_text(json.dumps({
        "config": "tiny-resnet", "traffic": "tiny-dp4-b16", "chips": 4,
        "why": "throw-away cell of the tests",
        "limits": limits["tiny-resnet-train"]}))
    return str(dst)


def test_data_parallel_driver_runs_on_four_virtual_devices(dp4_copy):
    """``train_dp`` wraps the tiny ResNet in ``ParallelWrapper`` over four
    (virtual CPU) devices: correct against the unchanged reference at the
    global batch, and nothing compiles inside the window."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), dp4_copy,
         "tiny-resnet-dp4", "2147483659", "0.5", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        cwd=os.path.dirname(dp4_copy), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert "compiles inside the window: 0 backend" in r.stderr
    assert out["correct"] is True, r.stderr[-2000:]


@pytest.mark.parametrize("fault", ["shard_alone", "half_batch",
                                   "state_unchanged"])
def test_broken_data_parallel_path_is_not_correct(dp4_copy, fault):
    """``shard_alone``: what chip 0 would hold had the chips exchanged
    nothing (every shard of a batch repeats the first): the limits notice
    the all-reduce's absence."""
    assert_not_correct(*drive_fault(dp4_copy, "tiny-resnet-dp4", fault,
                                    devices=4))
