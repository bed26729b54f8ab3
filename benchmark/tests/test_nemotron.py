"""The fifth language-model cell's files: the configuration against the
catalog's row, the operations its reference lists against a hand count, the
costs and readers of a Mamba-2 mixer's core, of two-matrix experts and of
the full core on a made-up ``ctx``, and the token driver on the CPU at a
tiny size with each planted fault coming out as not correct under the
cell's own driver."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT
from test_harness import KEYS, drive
from test_keye import CATALOG
from test_lfm2 import FWD, BACK, PEAK, manifest
from test_tokens import TINY_LIMITS, assert_not_correct, drive_fault

import costs
import costs_ssd
import costs_window
import flops
import run
import scope_reduce

CELL = "nemotron-3-nano-30b-a3b-ep16-train-seq16384"
CONFIG = "nemotron-3-nano-30b-a3b-ep16"
OWN = {"kernel.ssd_roofline", "step.ssm_mixer_ms",
       f"kernel.full_core_roofline.{CONFIG}",
       f"kernel.grouped_matmul_roofline.{CONFIG}"}
SHARED = {f"{name}.{CONFIG}" for name in (
    "kernel.dense_roofline", "step.moe_ms", "moe.expert_load_max_over_mean")}
#: where an op of the chunked scan lands inside the map over the groups
SCAN = "/attn/ssd/while/body/closed_call/checkpoint/scan/dot_general"


def test_configuration_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    cell = run.load_cell(CELL)
    cfg = cell["config"]
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == row["source_url"]
    src = row["config"]
    changed = {k for k, v in src.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: src[k] for k in cfg["reduced"]}
    assert set(cfg["held"]) >= set(cfg["reduced"]) and cfg["deployment"]
    kw = cfg["builder"]["kwargs"]
    # the layers held: the source's 34-42, one whole period
    assert kw["pattern"] == src["hybrid_override_pattern"][34:43] == (
        "EMEMEMEM*")
    assert len(kw["pattern"]) == cfg["num_hidden_layers"]
    assert kw["experts_held"] == [0, cfg["n_routed_experts"]]
    assert kw["vocab_rows"] == cfg["vocab_size"] == src["vocab_size"] // 8
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("ssm_heads", "mamba_num_heads"),
                         ("ssm_head_dim", "mamba_head_dim"),
                         ("ssm_state", "ssm_state_size"),
                         ("ssm_groups", "n_groups"),
                         ("ssm_chunk", "chunk_size"),
                         ("conv_kernel", "conv_kernel"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("shared_intermediate_size",
                          "moe_shared_expert_intermediate_size"),
                         ("n_router_outputs", "n_routed_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("routed_scaling_factor", "routed_scaling_factor"),
                         ("rms_norm_eps", "norm_eps")):
        assert kw[ours] == src[theirs], ours
    assert kw["seq_len"] == cell["traffic"]["seq_len"] == 16384
    assert cell["traffic"]["batch"] == 1
    # an expert sees 16,384 x 6 / 128 rows a step: a sixteenth of a
    # deployment's
    assert kw["seq_len"] * kw["experts_per_token"] // 128 == 768


def test_reference_lists_the_operations_of_a_hand_count():
    """38.4 TFLOP a step of one 16,384-token sequence over nine layers, by
    hand: the Mamba-2 projections 15.22, the shared experts 7.85, the core
    6.60, the head 4.33, attention's projections 2.30, routed experts 1.47,
    the chunked scan's products 0.54 (causal pairs within a chunk of 128),
    routers 0.14."""
    from reference import nemotron_h as ref

    cfg = run.load_cell(CELL)["config"]
    kw = cfg["builder"]["kwargs"]
    T, F, d, gn = 16384, 2688, 4096, 1024
    pairs = 128 * (128 * 129 // 2)
    hand = {
        "mamba_projections": 4 * 6 * T * (F * (2 * d + 2 * gn + 64) + d * F),
        "shared": 4 * 6 * T * F * 2 * 3712,
        "core": 6 * 32 * (T * (T + 1) // 2) * 2 * 128,
        "head": 6 * T * F * 16384,
        "attn_projections": 6 * T * (F * (4096 + 2 * 256) + 4096 * F),
        "routed": 4 * 6 * (T * 6 * 8 // 128) * F * 2 * 1856,
        "scan": 4 * 6 * (pairs * (8 * 128 + 64 * 64)
                         + 2 * T * 64 * 64 * 128),
        "routers": 4 * 6 * T * F * 128}
    assert {k: round(v / 1e12, 2) for k, v in hand.items()} == {
        "mamba_projections": 15.22, "shared": 7.85, "core": 6.60,
        "head": 4.33, "attn_projections": 2.30, "routed": 1.47,
        "scan": 0.54, "routers": 0.14}
    need = flops.train_flops_of(cfg)
    assert need == sum(hand.values()) and round(need / 1e12, 1) == 38.4
    assert flops.train_flops_by_scope(cfg) == {
        None: (hand["mamba_projections"] + hand["shared"] + hand["head"]
               + hand["attn_projections"] + hand["routers"]),
        "attn/ssd": hand["scan"], "attn/core": hand["core"],
        "moe/experts": hand["routed"]}
    n = sum(math.prod(s) for s in ref._shapes(ref._cfg(kw)).values())
    assert round(n / 1e6, 2) == 666.96
    assert round(16 * n / 1e9, 2) == 10.67                 # GB with Adam
    # the costs a reader divides by are the same counts
    assert costs_window.masked_core(1, 32, 2, T, 128)[0] == hand["core"]
    assert 4 * costs_ssd.ssd_core(T, T, 64, 64, 8, 128, 128)[0] == hand["scan"]
    assert 4 * costs_ssd.relu2_experts(6144, F, 1856, 8)[0] == hand["routed"]


def test_the_core_of_a_mixer_is_bound_by_its_bytes():
    """78,208 B a token a layer: W_in's output (10,304 wide) read forward,
    read again and its gradient written backward, and y (4,096) written and
    its gradient read; 1.56 ms a layer at 819 GB/s against 0.69 of
    operations."""
    f, b = costs_ssd.ssd_core(16384, 16384, 64, 64, 8, 128, 128)
    assert b == 16384 * 78208
    assert costs.least_seconds(f, b, PEAK) == b / PEAK["hbm_bytes_per_s"]
    assert round(1e3 * b / PEAK["hbm_bytes_per_s"], 2) == 1.56
    assert round(1e3 * f / PEAK["bf16_flops_per_s"], 2) == 0.69
    # a partial last chunk counts its own causal pairs
    assert costs_ssd.chunk_pairs(20, 8) == 2 * 36 + 10


def test_blocks_and_their_scopes():
    kw = run.load_cell(CELL)["config"]["builder"]["kwargs"]
    assert costs_ssd.blocks_of(kw, costs_ssd.MAMBA) == [1, 3, 5, 7]
    assert costs_ssd.blocks_of(kw, costs_ssd.ATTENTION) == [8]
    lfm2 = run.load_cell("lfm2-24b-a2b-ep8-train-seq32768")
    assert costs_ssd.blocks_of(lfm2["config"]["builder"]["kwargs"],
                               costs_ssd.ATTENTION) == []
    core, scan = re.compile(costs_ssd.SCOPE), re.compile(costs_ssd.SCAN_SCOPE)
    assert core.search(FWD.format(2) + SCAN) and scan.search(FWD.format(2)
                                                             + SCAN)
    assert core.search(BACK.format(4) + "/attn/ssd/mul")
    assert not scan.search(BACK.format(4) + "/attn/ssd/mul")
    assert not scan.search(FWD.format(2) + "/attn/ssd/while/body/jit("
                           "cumsum)/ssd_scan")
    assert not core.search(FWD.format(2) + "/attn/dot_general")


def test_the_cell_reads_its_listed_metrics_and_the_unlisted_ones():
    unlisted = {p["name"] for p in manifest()["per_layer"]
                if "workloads" not in p}
    names = {d["name"] for d, _ in run.load_metrics(CELL)}
    assert names == OWN | SHARED | unlisted
    for other in ("resnet50-train-b128", "deepseek-v2-lite-ep8-train-seq4096",
                  "trinity-mini-ep8-train-seq8192",
                  "keye-vl2-30b-a3b-ep8-train-seq16384",
                  "lfm2-24b-a2b-ep8-train-seq32768"):
        assert not (OWN | SHARED) & {
            d["name"] for d, _ in run.load_metrics(other)}


def test_the_shared_readers_are_the_accepted_ones():
    for name in SHARED:
        base = name[:-len(CONFIG) - 1]
        with open(os.path.join(BENCH, "metrics", base + ".json")) as f:
            want = json.load(f)
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            got = json.load(f)
        assert got.pop("workloads") == [CELL] and want.pop("workloads")
        assert got == want
        src = open(os.path.join(BENCH, "metrics", name + ".py")).read()
        assert f'accepted_reader("{base}")' in src


def reader(name):
    return next(read for d, read in run.load_metrics(CELL)
                if d["name"] == name)


@pytest.fixture()
def ctx(monkeypatch):
    """The cell with a made-up table of device events
    (``scope_reduce._events``'s ``[(op_name, ms a step)]``)."""
    events = []
    monkeypatch.setattr(scope_reduce, "_events", lambda ctx: events)
    counters = {f"{costs_ssd.TOKENS}{{layer={i}}}": 4 * 16384.0
                for i in (2, 4, 6, 8)}
    counters.update({f"dl4j_moe_routed_rows_total{{layer={i}}}": 4 * 6144.0
                     for i in (1, 3, 5, 7)})
    return {"cell": run.load_cell(CELL), "peak": PEAK,
            "device": {"count": 1}, "events": events, "counters": counters,
            "window": {"steps": 4}}


def _need_ms(ctx, scope):
    by_scope = flops.train_flops_by_scope(ctx["cell"]["config"])
    return 1e3 * by_scope[scope] / PEAK["bf16_flops_per_s"]


def test_a_share_cannot_pass_100_when_the_work_runs_at_its_bound(ctx):
    ssd = 4 * 1e3 * costs.least_seconds(
        *costs_ssd.ssd_core(16384, 16384, 64, 64, 8, 128, 128), PEAK)
    experts = 4 * 1e3 * costs.least_seconds(
        *costs_ssd.relu2_experts(6144, 2688, 1856, 8), PEAK)
    core, dense = _need_ms(ctx, "attn/core"), _need_ms(ctx, None)
    ctx["events"] += [
        (FWD.format(2) + SCAN, 0.25 * ssd),
        (BACK.format(4) + "/attn/ssd/mul", 0.75 * ssd),
        (FWD.format(9) + "/attn/core/pallas_call", 0.4 * core),
        (BACK.format(9) + "/attn/core/pallas_call", 0.6 * core),
        (FWD.format(1) + "/moe/experts/custom-call", experts),
        (FWD.format(6) + "/attn/dot_general", dense)]
    assert reader("kernel.ssd_roofline")(ctx) == pytest.approx(100.0)
    assert reader(f"kernel.full_core_roofline.{CONFIG}")(
        ctx) == pytest.approx(100.0)
    assert reader(f"kernel.grouped_matmul_roofline.{CONFIG}")(
        ctx) == pytest.approx(100.0)
    assert reader(f"kernel.dense_roofline.{CONFIG}")(ctx) == pytest.approx(
        100.0)
    # the four mixers whole: their cores and their products
    assert reader("step.ssm_mixer_ms")(ctx) == pytest.approx(ssd + dense)
    # a recomputed forward is time and not work
    ctx["events"].append((BACK.format(8) + "/rematted_computation" + SCAN,
                          ssd))
    share = reader("kernel.ssd_roofline")(ctx)
    assert share == pytest.approx(50.0)
    assert share.operands == pytest.approx(
        {"least_s": ssd / 1e3, "device_s": 2 * ssd / 1e3})


def test_the_core_is_read_from_the_tokens_the_program_counted(ctx):
    ssd = 1e3 * costs.least_seconds(
        *costs_ssd.ssd_core(16384, 16384, 64, 64, 8, 128, 128), PEAK)
    ctx["events"].append((FWD.format(2) + SCAN, 4 * ssd))
    ctx["counters"] = {f"{costs_ssd.TOKENS}{{layer=2}}": 4 * 16384.0}
    assert reader("kernel.ssd_roofline")(ctx) == pytest.approx(25.0)
    ctx["counters"] = {}
    assert reader("kernel.ssd_roofline")(ctx) is None


def test_readers_return_nothing_where_the_program_has_nothing():
    """On a cell without Mamba-2 mixers (or a program without the scopes and
    counters, as the parent commit is) every reader this cell brings returns
    None and raises nothing."""
    for name in ("lfm2-24b-a2b-ep8-train-seq32768", CELL):
        ctx = {"cell": run.load_cell(name), "counters": {}, "trace": {},
               "window": {"steps": 4}, "peak": PEAK, "device": {"count": 1}}
        ctx["cell"]["name"] = "no-such-profile"
        for desc, read in run.load_metrics(CELL):
            if desc["name"] in OWN | SHARED:
                assert read(ctx) is None, desc["name"]


# ------------------------------------------------- the tiny cell on the CPU
@pytest.fixture(scope="module")
def nemotron_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny Nemotron-H cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "nemotron"), dst,
                    dirs_exist_ok=True)
    (dst / "workloads" / "tiny-nemotron-train.json").write_text(json.dumps({
        "config": "tiny-nemotron", "traffic": "tiny-seq40-b1-nemotron",
        "chips": 1, "why": "throw-away cell of the tests",
        "limits": TINY_LIMITS}))
    for name in OWN | SHARED:
        path = dst / "metrics" / f"{name}.json"
        desc = json.loads(path.read_text())
        desc["workloads"].append("tiny-nemotron-train")
        path.write_text(json.dumps(desc))
    return str(dst)


def test_tiny_cell_runs_and_is_correct_on_the_cpu(nemotron_copy):
    out, err = drive(nemotron_copy, "tiny-nemotron-train", 2147483659)
    assert KEYS <= set(out) and out["correct"] is True, err[-2000:]
    assert "compiles inside the window: 0 backend" in err
    line = next(l for l in err.splitlines() if "rows routed" in l)
    prog, ref = line.split("program ")[1].split(", reference ")
    assert [n for _, n in eval(prog)] == eval(ref)


def test_traced_tiny_cell_reads_the_expert_counters(nemotron_copy):
    """On the CPU no scope is traced on a device: the readers of device time
    say nothing, the routing's counter is read."""
    out, _ = drive(nemotron_copy, "tiny-nemotron-train", 7, trace=1)
    m = out["metrics"]
    assert m[f"moe.expert_load_max_over_mean.{CONFIG}"]["value"] >= 1.0
    assert not OWN & set(m)


def drive_nemotron_fault(bench, cell, fault):
    """One run with ``fault`` planted under the cell's own driver. The
    executable store is off: its key holds the configuration, not the code,
    and most of the faults change the code alone."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_nemotron_faults.py"),
         bench, cell, "2147483659", "0.5", fault],
        env=dict(os.environ, JAX_PLATFORMS="cpu", DL4J_COMPILE_CACHE="0"),
        cwd=os.path.dirname(bench), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.parametrize("fault", ["no_decay", "no_carry", "no_z_gate",
                                   "relu_unsquared", "no_bias_step",
                                   "half_batch"])
def test_each_planted_fault_is_not_correct(nemotron_copy, fault):
    assert_not_correct(*drive_nemotron_fault(nemotron_copy,
                                             "tiny-nemotron-train", fault))


def test_state_left_unchanged_is_not_correct(nemotron_copy):
    assert_not_correct(*drive_fault(nemotron_copy, "tiny-nemotron-train",
                                    "state_unchanged"))


def test_the_parent_program_fails_at_once_on_the_cell(nemotron_copy,
                                                      tmp_path):
    """A program without the configuration's builder exits non-zero at the
    driver's first line, before any weight is made."""
    fake = tmp_path / "deeplearning4j_tpu" / "models"
    shutil.copytree(os.path.join(ROOT, "deeplearning4j_tpu"),
                    tmp_path / "deeplearning4j_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.remove(fake / "nemotron_h.py")
    init = (fake / "__init__.py").read_text()
    (fake / "__init__.py").write_text(init.replace(
        "from deeplearning4j_tpu.models.nemotron_h import nemotron_h\n", ""))
    bench = tmp_path / "benchmark"
    shutil.copytree(nemotron_copy, bench)
    code = (
        "import argparse, sys; sys.path[:0] = [{!r}, {!r}]; import jax, run; "
        "sys.exit(run.run(argparse.Namespace(workload='tiny-nemotron-train', "
        "seed=1, seconds=0.5, trace=0), find=lambda chips: ("
        "jax.devices()[:chips], {{'bf16_flops_per_s': 1e12, "
        "'hbm_bytes_per_s': 1e11}})))").format(str(tmp_path), str(bench))
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "nemotron_h" in r.stderr and "built and placed" not in r.stderr
