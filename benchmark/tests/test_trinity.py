"""The second language-model cell's files: the configuration against the
catalog's row, the operations its reference lists, the costs of a masked,
grouped core, its metrics, and the token driver with the reference's Adam
follower and bias update driven on the CPU at a tiny size."""
import json
import math
import os
import shutil

import pytest

from conftest import BENCH, HERE
from test_harness import KEYS, drive
from test_tokens import (BOTH_LM, TINY_LIMITS, UNLISTED, assert_not_correct,
                         drive_fault)

import costs
import costs_window
import flops
import run

CELL = "trinity-mini-ep8-train-seq8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"kernel.window_core_roofline", "step.attn_core_window_ms",
       "step.attn_core_full_ms", "attn.masked_work_pct"}


def test_configuration_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    cell = run.load_cell(CELL)
    cfg = cell["config"]
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Trinity-Mini")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    kw = cfg["builder"]["kwargs"]
    assert (kw["n_layers"], kw["n_dense_layers"], kw["experts_held"],
            kw["vocab_rows"]) == (cfg["num_hidden_layers"],
                                  cfg["num_dense_layers"],
                                  [0, cfg["num_experts"]], cfg["vocab_size"])
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("sliding_window", "sliding_window"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("n_router_outputs", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("n_shared_experts", "num_shared_experts"),
                         ("route_scale", "route_scale"),
                         ("load_balance_coeff", "load_balance_coeff"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("rope_theta", "rope_theta"),
                         ("mup_enabled", "mup_enabled")):
        assert kw[ours] == row["config"][theirs], ours
    # the layers held: the source's layer 0 and one whole period, 4-7
    source = row["config"]["layer_types"]
    assert kw["layer_types"] == [source[0]] + source[4:8]
    assert kw["vocab_rows"] * 8 == row["config"]["vocab_size"]
    assert kw["seq_len"] == cell["traffic"]["seq_len"] == 8192
    assert cell["traffic"]["batch"] * kw["seq_len"] == 16384


def test_reference_lists_the_operations_the_issue_counted():
    from reference import trinity_mini as ref

    cfg = run.load_cell(CELL)["config"]
    kw = cfg["builder"]["kwargs"]
    need = flops.train_flops_of(cfg)
    assert round(2 * need / 1e12, 1) == 36.3            # TFLOP a step
    share, c = {}, ref._cfg(kw)
    for l in ref.layers(kw):
        i, kind = l["name"].split("/")
        if kind == "core":
            kind = "window" if ref._window(c, int(i)) else "full"
        share[kind] = share.get(kind, 0) + 6 * l["nin"] * l["nout"] / need
    pct = {k: round(100 * v, 1) for k, v in share.items()}
    assert (pct["window"], pct["full"], pct["W"], pct["ffn"], pct["shared"],
            pct["routed"], pct["Wr"]) == (15.9, 9.1, 13.9, 10.2, 6.8, 6.8, 0.3)
    assert round(100 * sum(share[k] for k in ("Wq", "Wk", "Wv", "Wz",
                                              "Wo")), 1) == 36.9
    n = sum(math.prod(s) for s in ref._shapes(c).values())
    assert round(n / 1e6, 1) == 705.5
    assert round(16 * n / 1e9, 2) == 11.29                  # GB with Adam


@pytest.mark.parametrize("seq,window,pairs", [
    (8192, None, 33558528), (8192, 2048, 14681088), (8192, 8192, 33558528),
    (5, 2, 9), (4096, 2048, 6292480), (16, 6, 81)])
def test_visible_pairs_by_the_mask(seq, window, pairs):
    from reference import trinity_mini as ref

    assert costs_window.visible_pairs(seq, window) == pairs
    assert ref.visible_pairs(seq, window) == pairs
    assert pairs == sum(min(r + 1, window or seq) for r in range(seq))


def test_masked_core_at_equal_heads_and_no_window_is_costs_attention_core():
    assert costs_window.masked_core(4, 16, 16, 4096, 128) == (
        costs.attention_core(4, 16, 4096, 128, 128))
    flops_w, bytes_w = costs_window.masked_core(2, 32, 4, 8192, 128, 2048)
    flops_f, bytes_f = costs_window.masked_core(2, 32, 4, 8192, 128)
    assert round(flops_w / flops_f, 4) == 0.4375
    # a group's keys and values are read once: (2 x 32 + 2 x 4) of 4 x 32
    assert bytes_w == bytes_f == 3 * 2 * 8192 * 128 * 72 * 2
    peak = run.peak_for("TPU v5 lite")
    assert costs.least_seconds(flops_w, bytes_w, peak) == flops_w / 197e12


def test_block_windows_and_the_scope_of_a_block():
    kw = run.load_cell(CELL)["config"]["builder"]["kwargs"]
    assert costs_window.block_windows(kw) == [2048, 2048, 2048, 2048, None]
    assert costs_window.block_windows({"n_layers": 6}) == []
    import re

    rx = re.compile(costs_window.core_scope([0, 1, 2, 3]))
    name = ("transpose(jvp(layer/{0}_DecoderBlock))/jvp(layer/{0}_DecoderBlock)"
            "/checkpoint/rematted_computation/attn/core/reduce_sum")
    assert rx.search(name.format(1)) and rx.search(name.format(4))
    assert not rx.search(name.format(5)) and not rx.search(name.format(14))
    assert rx.search("jit(f)/while/body/closed_call/jvp(layer/2_DecoderBlock)"
                     "/attn/core/dot_general")
    assert not rx.search("jvp(layer/1_DecoderBlock)/attn/Wq/dot_general")


def test_the_cell_reads_its_listed_metrics_and_the_unlisted_eight():
    names = {d["name"] for d, _ in run.load_metrics(CELL)}
    assert names == NEW | BOTH_LM | UNLISTED
    for other in ("resnet50-train-b128", "vgg16-train-b128",
                  "deepseek-v2-lite-ep8-train-seq4096"):
        assert not NEW & {d["name"] for d, _ in run.load_metrics(other)}


def test_new_readers_return_nothing_where_the_program_has_nothing():
    """On a cell without ``layer_types`` (or a program without the
    counters) every new reader returns None and raises nothing."""
    ctx = {"cell": run.load_cell("deepseek-v2-lite-ep8-train-seq4096"),
           "counters": {}, "trace": {}, "window": {"steps": 8}}
    ctx["cell"]["name"] = "no-such-profile"
    for desc, read in run.load_metrics(CELL):
        if desc["name"] in NEW:
            assert read(ctx) is None, desc["name"]


@pytest.fixture(scope="module")
def trinity_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny Trinity cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "trinity"), dst,
                    dirs_exist_ok=True)
    (dst / "workloads" / "tiny-trinity-train.json").write_text(json.dumps({
        "config": "tiny-trinity", "traffic": "tiny-seq16-b2-int", "chips": 1,
        "why": "throw-away cell of the tests", "limits": TINY_LIMITS}))
    for name in NEW:
        path = dst / "metrics" / f"{name}.json"
        desc = json.loads(path.read_text())
        desc["workloads"].append("tiny-trinity-train")
        path.write_text(json.dumps(desc))
    return str(dst)


def test_tiny_cell_runs_and_is_correct_on_the_cpu(trinity_copy):
    out, err = drive(trinity_copy, "tiny-trinity-train", 2147483659)
    assert KEYS <= set(out) and out["correct"] is True, err[-2000:]
    assert "compiles inside the window: 0 backend" in err
    line = next(l for l in err.splitlines() if "rows routed" in l)
    prog, ref = line.split("program ")[1].split(", reference ")
    assert [n for _, n in eval(prog)] == eval(ref)


def test_traced_tiny_cell_reads_the_masked_work_counter(trinity_copy):
    """On the CPU the XLA math computes every block's whole square: 4
    sliding blocks see 81 of 256 entries a head, the full one 136."""
    out, _ = drive(trinity_copy, "tiny-trinity-train", 7, trace=1)
    m = out["metrics"]
    assert m["attn.masked_work_pct"]["value"] == pytest.approx(
        100 * (1 - (4 * 81 + 136) / (5 * 256)))
    assert not {"kernel.window_core_roofline", "step.attn_core_window_ms",
                "step.attn_core_full_ms"} & set(m)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_tiny_path_is_not_correct(trinity_copy, fault):
    assert_not_correct(*drive_fault(trinity_copy, "tiny-trinity-train",
                                    fault))
