"""The fourth language-model cell's files: the configuration against the
catalog's row, the operations its reference lists against a hand count, the
costs and readers of a gated short convolution and of the full core on a
made-up ``ctx``, and the token driver on the CPU at a tiny size with each
planted fault coming out as not correct under the cell's own driver."""
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
from test_tokens import TINY_LIMITS, assert_not_correct, drive_fault

import costs
import costs_conv
import costs_window
import flops
import run
import scope_reduce

CELL = "lfm2-24b-a2b-ep8-train-seq32768"
CONFIG = "lfm2-24b-a2b-ep8"
OWN = {"kernel.short_conv_roofline", "step.conv_mixer_ms",
       "kernel.full_core_roofline"}
SHARED = {f"{name}.{CONFIG}" for name in (
    "kernel.dense_roofline", "kernel.grouped_matmul_roofline", "step.moe_ms",
    "moe.expert_load_max_over_mean")}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FWD = "jit(dl4j_train_ksteps)/while/body/closed_call/jvp(layer/{}_DecoderBlock)"
BACK = ("jit(dl4j_train_ksteps)/while/body/closed_call/transpose(jvp(layer/"
        "{0}_DecoderBlock))/jvp(layer/{0}_DecoderBlock)/checkpoint")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    cell = run.load_cell(CELL)
    cfg = cell["config"]
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert set(cfg["held"]) >= set(cfg["reduced"]) and cfg["deployment"]
    kw, src = cfg["builder"]["kwargs"], row["config"]
    assert (kw["n_layers"], kw["n_dense_layers"], kw["experts_held"],
            kw["vocab_rows"]) == (cfg["num_hidden_layers"],
                                  cfg["num_dense_layers"],
                                  [0, cfg["num_experts"]], cfg["vocab_size"])
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("conv_kernel", "conv_L_cache"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("n_router_outputs", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("routed_scaling_factor", "routed_scaling_factor"),
                         ("rms_norm_eps", "norm_eps")):
        assert kw[ours] == src[theirs], ours
    assert kw["rope_theta"] == src["rope_parameters"]["rope_theta"]
    assert kw["head_dim"] * kw["n_heads"] == src["hidden_size"]
    # the layers held: the source's 0 and 2-5, one dense layer and a period
    assert kw["layer_types"] == [src["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert kw["vocab_rows"] * 8 == src["vocab_size"]
    assert kw["experts_held"][1] * 8 == src["num_experts"]
    assert kw["seq_len"] == cell["traffic"]["seq_len"] == 32768
    assert cell["traffic"]["batch"] == 1
    # an expert sees 32,768 x 4 / 64 rows a step: an eighth of a deployment's
    assert kw["seq_len"] * kw["experts_per_token"] // 64 == 2048


def test_reference_lists_the_operations_of_a_hand_count():
    """49.8 TFLOP a step of one 32,768-token sequence over five layers, by
    hand: the convolutions' projections 13.19, the core 13.19, the dense
    feed-forward 14.22, routed experts 3.71, the head 3.30, attention's
    projections 2.06, routers 0.10, the taps and gates 0.01."""
    from reference import lfm2_moe as ref

    cfg = run.load_cell(CELL)["config"]
    kw = cfg["builder"]["kwargs"]
    T, F = 32768, 2048
    hand = {
        "conv_projections": 4 * 6 * T * F * (3 * F + F),
        "core": 6 * 32 * (T * (T + 1) // 2) * 2 * 64,
        "dense": 6 * T * F * 3 * 11776,
        "routed": 4 * 6 * (T * 4 * 8 // 64) * F * 3 * 1536,
        "head": 6 * T * F * 8192,
        "attn_projections": 6 * T * F * (2048 + 512 + 512 + 2048),
        "routers": 4 * 6 * T * F * 64,
        "taps": 4 * 6 * T * F * 4}
    assert {k: round(v / 1e12, 2) for k, v in hand.items()} == {
        "conv_projections": 13.19, "core": 13.19, "dense": 14.22,
        "routed": 3.71, "head": 3.30, "attn_projections": 2.06,
        "routers": 0.10, "taps": 0.01}
    need = flops.train_flops_of(cfg)
    assert need == sum(hand.values()) and round(need / 1e12, 1) == 49.8
    assert flops.train_flops_by_scope(cfg) == {
        None: (hand["conv_projections"] + hand["dense"] + hand["head"]
               + hand["attn_projections"] + hand["routers"]),
        "attn/conv": hand["taps"], "attn/core": hand["core"],
        "moe/experts": hand["routed"]}
    n = sum(math.prod(s) for s in ref._shapes(ref._cfg(kw)).values())
    assert round(n / 1e6, 2) == 486.06
    assert round(16 * n / 1e9, 2) == 7.78                  # GB with Adam
    # the costs a reader divides by are the same counts
    core = costs_window.masked_core(1, 32, 8, T, 64)
    assert core[0] == hand["core"]
    assert 4 * costs_conv.gated_conv(T, F, 3)[0] == hand["taps"]


def test_the_gated_convolution_is_bound_by_its_bytes():
    f, b = costs_conv.gated_conv(32768, 2048, 3)
    assert b == 11 * 32768 * 2048 * 2
    assert costs.least_seconds(f, b, PEAK) == b / PEAK["hbm_bytes_per_s"]
    assert round(1e3 * b / PEAK["hbm_bytes_per_s"], 2) == 1.80  # ms a layer


def test_blocks_and_their_scopes():
    kw = run.load_cell(CELL)["config"]["builder"]["kwargs"]
    assert costs_conv.blocks_of(kw, costs_conv.CONV) == [0, 2, 3, 4]
    assert costs_conv.blocks_of(kw, costs_conv.FULL) == [1]
    trinity = run.load_cell("trinity-mini-ep8-train-seq8192")
    assert costs_conv.blocks_of(trinity["config"]["builder"]["kwargs"],
                                costs_conv.FULL) == []
    rx = re.compile(costs_conv.mixer_scope([0, 2, 3, 4]))
    assert rx.search(FWD.format(1) + "/attn/dot_general")
    assert rx.search(BACK.format(5) + "/rematted_computation/attn/conv/mul")
    assert not rx.search(FWD.format(2) + "/attn/core/pallas_call")
    assert not rx.search(FWD.format(1) + "/ffn/dot_general")
    assert not rx.search(FWD.format(3) + "/moe/router/dot_general")


def test_the_cell_reads_its_listed_metrics_and_the_unlisted_ones():
    unlisted = {p["name"] for p in manifest()["per_layer"]
                if "workloads" not in p}
    names = {d["name"] for d, _ in run.load_metrics(CELL)}
    assert names == OWN | SHARED | unlisted
    for other in ("resnet50-train-b128", "deepseek-v2-lite-ep8-train-seq4096",
                  "trinity-mini-ep8-train-seq8192",
                  "keye-vl2-30b-a3b-ep8-train-seq16384"):
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
    return {"cell": run.load_cell(CELL), "peak": PEAK,
            "device": {"count": 1}, "events": events, "counters": {
                f"{costs_conv.TOKENS}{{layer={i}}}": 4 * 32768.0
                for i in (1, 3, 4, 5)},
            "window": {"steps": 4}}


def _need_ms(ctx, scope):
    by_scope = flops.train_flops_by_scope(ctx["cell"]["config"])
    return 1e3 * by_scope[scope] / PEAK["bf16_flops_per_s"]


def test_a_share_cannot_pass_100_when_the_work_runs_at_its_bound(ctx):
    conv = 4 * 1e3 * costs.least_seconds(
        *costs_conv.gated_conv(32768, 2048, 3), PEAK)
    core, dense = _need_ms(ctx, "attn/core"), _need_ms(ctx, None)
    ctx["events"] += [
        (FWD.format(1) + "/attn/conv/mul", 0.25 * conv),
        (BACK.format(3) + "/attn/conv/mul", 0.75 * conv),
        (FWD.format(2) + "/attn/core/pallas_call", 0.4 * core),
        (BACK.format(2) + "/attn/core/pallas_call", 0.6 * core),
        (FWD.format(4) + "/attn/dot_general", dense)]
    assert reader("kernel.short_conv_roofline")(ctx) == pytest.approx(100.0)
    assert reader("kernel.full_core_roofline")(ctx) == pytest.approx(100.0)
    assert reader(f"kernel.dense_roofline.{CONFIG}")(ctx) == pytest.approx(
        100.0)
    # the mixers of the four convolutions: their taps and their products
    assert reader("step.conv_mixer_ms")(ctx) == pytest.approx(conv + dense)
    # a recomputed forward is time and not work
    ctx["events"].append((BACK.format(5) + "/rematted_computation/attn/conv"
                          "/mul", conv))
    share = reader("kernel.short_conv_roofline")(ctx)
    assert share == pytest.approx(50.0)
    assert share.operands == pytest.approx(
        {"least_s": conv / 1e3, "device_s": 2 * conv / 1e3})


def test_the_taps_are_read_from_the_tokens_the_program_counted(ctx):
    conv = 1e3 * costs.least_seconds(*costs_conv.gated_conv(32768, 2048, 3),
                                     PEAK)
    ctx["events"].append((FWD.format(1) + "/attn/conv/mul", 4 * conv))
    ctx["counters"] = {f"{costs_conv.TOKENS}{{layer=1}}": 4 * 32768.0}
    assert reader("kernel.short_conv_roofline")(ctx) == pytest.approx(25.0)
    ctx["counters"] = {}
    assert reader("kernel.short_conv_roofline")(ctx) is None


def test_readers_return_nothing_where_the_program_has_nothing():
    """On a cell without convolutions (or a program without the scopes and
    counters, as the parent commit is) every reader this cell brings returns
    None and raises nothing."""
    for name in ("trinity-mini-ep8-train-seq8192", CELL):
        ctx = {"cell": run.load_cell(name), "counters": {}, "trace": {},
               "window": {"steps": 4}, "peak": PEAK, "device": {"count": 1}}
        ctx["cell"]["name"] = "no-such-profile"
        for desc, read in run.load_metrics(CELL):
            if desc["name"] in OWN | SHARED:
                assert read(ctx) is None, desc["name"]


# ------------------------------------------------- the tiny cell on the CPU
@pytest.fixture(scope="module")
def lfm2_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny LFM2 cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "lfm2"), dst,
                    dirs_exist_ok=True)
    (dst / "workloads" / "tiny-lfm2-train.json").write_text(json.dumps({
        "config": "tiny-lfm2", "traffic": "tiny-seq32-b1-lfm2", "chips": 1,
        "why": "throw-away cell of the tests", "limits": TINY_LIMITS}))
    for name in OWN | SHARED:
        path = dst / "metrics" / f"{name}.json"
        desc = json.loads(path.read_text())
        desc["workloads"].append("tiny-lfm2-train")
        path.write_text(json.dumps(desc))
    return str(dst)


def test_tiny_cell_runs_and_is_correct_on_the_cpu(lfm2_copy):
    out, err = drive(lfm2_copy, "tiny-lfm2-train", 2147483659)
    assert KEYS <= set(out) and out["correct"] is True, err[-2000:]
    assert "compiles inside the window: 0 backend" in err
    line = next(l for l in err.splitlines() if "rows routed" in l)
    prog, ref = line.split("program ")[1].split(", reference ")
    assert [n for _, n in eval(prog)] == eval(ref)


def test_traced_tiny_cell_reads_the_expert_counters(lfm2_copy):
    """On the CPU no scope is traced on a device: the readers of device time
    say nothing, the routing's counter is read."""
    out, _ = drive(lfm2_copy, "tiny-lfm2-train", 7, trace=1)
    m = out["metrics"]
    assert m[f"moe.expert_load_max_over_mean.{CONFIG}"]["value"] >= 1.0
    assert not OWN & set(m)


def drive_lfm2_fault(bench, cell, fault):
    """One run with ``fault`` planted under the cell's own driver. The
    executable store is off: its key holds the configuration, not the code,
    and two of the faults change the code alone."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_lfm2_faults.py"), bench,
         cell, "2147483659", "0.5", fault],
        env=dict(os.environ, JAX_PLATFORMS="cpu", DL4J_COMPILE_CACHE="0"),
        cwd=os.path.dirname(bench), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.parametrize("fault", ["taps_reversed", "no_cg_gate",
                                   "no_bias_step", "half_batch"])
def test_each_planted_fault_is_not_correct(lfm2_copy, fault):
    assert_not_correct(*drive_lfm2_fault(lfm2_copy, "tiny-lfm2-train", fault))


def test_state_left_unchanged_is_not_correct(lfm2_copy):
    assert_not_correct(*drive_fault(lfm2_copy, "tiny-lfm2-train",
                                    "state_unchanged"))


def test_the_parent_program_fails_at_once_on_the_cell(lfm2_copy, tmp_path):
    """A program without the configuration's builder exits non-zero at the
    driver's first line, before any weight is made."""
    fake = tmp_path / "deeplearning4j_tpu" / "models"
    shutil.copytree(os.path.join(ROOT, "deeplearning4j_tpu"),
                    tmp_path / "deeplearning4j_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.remove(fake / "lfm2_moe.py")
    init = (fake / "__init__.py").read_text()
    (fake / "__init__.py").write_text(init.replace(
        "from deeplearning4j_tpu.models.lfm2_moe import lfm2_moe\n", ""))
    bench = tmp_path / "benchmark"
    shutil.copytree(lfm2_copy, bench)
    code = (
        "import argparse, sys; sys.path[:0] = [{!r}, {!r}]; import jax, run; "
        "sys.exit(run.run(argparse.Namespace(workload='tiny-lfm2-train', "
        "seed=1, seconds=0.5, trace=0), find=lambda chips: ("
        "jax.devices()[:chips], {{'bf16_flops_per_s': 1e12, "
        "'hbm_bytes_per_s': 1e11}})))").format(str(tmp_path), str(bench))
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "lfm2_moe" in r.stderr and "built and placed" not in r.stderr
