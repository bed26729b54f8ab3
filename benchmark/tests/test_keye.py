"""The third language-model cell's files: the configuration against the
catalog's row, the operations its reference lists against a hand count, the
costs and readers of an attention that selects its keys on a made-up ``ctx``,
and the token driver on the CPU at a tiny size with each planted fault coming
out as not correct under the cell's own driver."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE
from test_harness import KEYS, drive
from test_tokens import TINY_LIMITS, UNLISTED, assert_not_correct, drive_fault

import costs_sparse
import flops
import run
import scope_reduce

CELL = "keye-vl2-30b-a3b-ep8-train-seq16384"
CONFIG = "keye-vl2-30b-a3b-ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"kernel.sparse_core_roofline", "kernel.indexer_roofline",
       "step.attn_select_ms", "attn.unselected_work_pct"}
SHARED = {f"{name}.{CONFIG}" for name in (
    "kernel.dense_roofline", "kernel.grouped_matmul_roofline",
    "step.attention_ms", "step.moe_ms", "moe.padded_rows_pct",
    "moe.expert_load_max_over_mean")}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BLOCK = "jit(dl4j_train_ksteps)/while/body/closed_call/jvp(layer/3_DecoderBlock)"
BACK = ("jit(dl4j_train_ksteps)/while/body/closed_call/transpose(jvp(layer/"
        "3_DecoderBlock))/jvp(layer/3_DecoderBlock)/checkpoint")


def test_configuration_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    cell = run.load_cell(CELL)
    cfg = cell["config"]
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert set(cfg["held"]) >= set(cfg["reduced"]) and cfg["deployment"]
    kw, sa = cfg["builder"]["kwargs"], row["config"]["sa_config"]
    assert (kw["n_layers"], kw["experts_held"], kw["vocab_rows"]) == (
        cfg["num_hidden_layers"], [0, cfg["num_experts"]], cfg["vocab_size"])
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("n_router_outputs", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("norm_topk_prob", "norm_topk_prob"),
                         ("rms_norm_eps", "rms_norm_eps"),
                         ("rope_theta", "rope_theta")):
        assert kw[ours] == row["config"][theirs], ours
    assert (kw["index_n_heads"], kw["index_head_dim"], kw["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert sa["indexer_num_kv_heads"] == 1
    assert kw["vocab_rows"] * 8 == row["config"]["vocab_size"]
    assert kw["seq_len"] == cell["traffic"]["seq_len"] == 16384
    assert cell["traffic"]["batch"] == 1


def test_reference_lists_the_operations_the_issue_counted():
    """26.4 TFLOP a step of one 16,384-token sequence over five layers, by
    hand: projections 9.28, selected cores 7.73, head 3.82, routed experts
    2.32, index scores 2.02, indexer projections 1.11, routers 0.13."""
    from reference import keye_vl2 as ref

    cfg = run.load_cell(CELL)["config"]
    kw = cfg["builder"]["kwargs"]
    T, F, L = 16384, 2048, 5
    chosen = 2048 * 2049 // 2 + (T - 2048) * 2048
    causal = T * (T + 1) // 2
    assert chosen == 31458304 == ref.selected_pairs(T, 2048)
    hand = {
        "projections": L * 6 * T * F * (4096 + 512 + 512 + 4096),
        "cores": L * 6 * 32 * chosen * 2 * 128,
        "head": 6 * T * F * 18992,
        "routed": L * 6 * (T * 8 * 16 // 128) * F * 3 * 768,
        "index": L * 2 * 16 * 64 * (causal + 2 * chosen),
        "index_projections": L * 6 * T * F * (1024 + 64 + 16),
        "routers": L * 6 * T * F * 128}
    assert {k: round(v / 1e12, 2) for k, v in hand.items()} == {
        "projections": 9.28, "cores": 7.73, "head": 3.82, "routed": 2.32,
        "index": 2.02, "index_projections": 1.11, "routers": 0.13}
    need = flops.train_flops_of(cfg)
    assert need == sum(hand.values()) and round(need / 1e12, 1) == 26.4
    by_scope = flops.train_flops_by_scope(cfg)
    assert by_scope == {
        None: (hand["projections"] + hand["head"] + hand["routers"]
               + hand["index_projections"]),
        "attn/core": hand["cores"], "attn/indexer": hand["index"],
        "moe/experts": hand["routed"]}
    n = sum(math.prod(s) for s in ref._shapes(ref._cfg(kw)).values())
    assert round(n / 1e6, 1) == 562.3
    assert round(16 * n / 1e9, 2) == 9.0                   # GB with Adam
    # the costs a reader divides by are the same counts
    core = costs_sparse.selected_core(1, 32, 4, T, 128, 2048)
    assert L * core[0] == hand["cores"]
    assert L * costs_sparse.index_scores(1, 16, 64, T, 2048)[0] == (
        hand["index"])
    assert core[1] == 3 * T * 128 * (2 * 32 + 2 * 4) * 2


@pytest.mark.parametrize("seq,topk,pairs", [
    (16384, 2048, 31458304), (16, 5, 70), (5, 8, 15), (2048, 2048, 2098176)])
def test_selected_pairs_by_the_count(seq, topk, pairs):
    from reference import keye_vl2 as ref

    assert costs_sparse.selected_pairs(seq, topk) == pairs
    assert ref.selected_pairs(seq, topk) == pairs
    assert pairs == sum(min(t + 1, topk) for t in range(seq))
    assert costs_sparse.causal_pairs(seq) == seq * (seq + 1) // 2


def test_the_cell_reads_its_listed_metrics_and_the_unlisted_eight():
    names = {d["name"] for d, _ in run.load_metrics(CELL)}
    assert names == OWN | SHARED | UNLISTED
    for other in ("resnet50-train-b128", "deepseek-v2-lite-ep8-train-seq4096",
                  "trinity-mini-ep8-train-seq8192"):
        assert not (OWN | SHARED) & {
            d["name"] for d, _ in run.load_metrics(other)}


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
            "device": {"count": 1}, "events": events, "counters": {},
            "window": {"steps": 4}}


def _need_ms(ctx, scope):
    by_scope = flops.train_flops_by_scope(ctx["cell"]["config"])
    return 1e3 * by_scope[scope] / PEAK["bf16_flops_per_s"]


def test_a_share_cannot_pass_100_when_the_required_operations_run_at_peak(
        ctx):
    core, index = _need_ms(ctx, "attn/core"), _need_ms(ctx, "attn/indexer")
    dense = _need_ms(ctx, None)
    ctx["events"] += [(BLOCK + "/attn/core/pallas_call", 0.4 * core),
                      (BACK + "/attn/core/pallas_call", 0.6 * core),
                      (BLOCK + "/attn/indexer/pallas_call", 0.5 * index),
                      (BACK + "/attn/indexer/pallas_call", 0.5 * index),
                      (BLOCK + "/attn/dot_general", dense)]
    assert reader("kernel.sparse_core_roofline")(ctx) == pytest.approx(100.0)
    assert reader("kernel.indexer_roofline")(ctx) == pytest.approx(100.0)
    assert reader(f"kernel.dense_roofline.{CONFIG}")(ctx) == pytest.approx(
        100.0)
    assert reader("step.attn_select_ms")(ctx) is None
    # the masked-dense plan's time at pairs the selection hides, and the
    # backward's recomputed scores, are time and not work
    ctx["events"].append((BACK + "/rematted_computation/attn/core/pallas_call",
                          3 * core))
    share = reader("kernel.sparse_core_roofline")(ctx)
    assert share == pytest.approx(25.0)
    assert share.operands == pytest.approx(
        {"least_s": core / 1e3, "device_s": 4 * core / 1e3})


def test_the_selections_time_is_the_indexers_and_not_the_dense_products(ctx):
    index, dense = _need_ms(ctx, "attn/indexer"), _need_ms(ctx, None)
    ctx["events"] += [(BLOCK + "/attn/indexer/pallas_call", index),
                      (BLOCK + "/attn/indexer/select/pallas_call", index),
                      (BACK + "/rematted_computation/attn/indexer/select"
                       "/pallas_call", 2 * index),
                      (BLOCK + "/attn/dot_general", 2 * dense)]
    assert reader("step.attn_select_ms")(ctx) == pytest.approx(3 * index)
    assert reader("kernel.indexer_roofline")(ctx) == pytest.approx(25.0)
    assert reader(f"kernel.dense_roofline.{CONFIG}")(ctx) == pytest.approx(
        50.0)
    assert reader(f"step.attention_ms.{CONFIG}")(ctx) == pytest.approx(
        4 * index + 2 * dense)


def test_unselected_work_is_read_from_the_two_counters(ctx):
    assert reader("attn.unselected_work_pct")(ctx) is None
    ctx["counters"] = {
        "dl4j_attn_score_entries_visible_total{layer=1}": 31458304.0 * 32,
        "dl4j_attn_score_entries_computed_total{layer=1}": 134225920.0 * 32,
        "dl4j_attn_score_entries_visible_total{layer=2}": 31458304.0 * 32,
        "dl4j_attn_score_entries_computed_total{layer=2}": 134225920.0 * 32}
    assert reader("attn.unselected_work_pct")(ctx) == pytest.approx(
        100 * (1 - 31458304 / 134225920))


def test_readers_return_nothing_where_the_program_has_nothing():
    """On a cell without an indexer (or a program without the scopes and
    counters, as the parent commit is) every reader this cell brings returns
    None and raises nothing."""
    ctx = {"cell": run.load_cell("trinity-mini-ep8-train-seq8192"),
           "counters": {}, "trace": {}, "window": {"steps": 8},
           "peak": PEAK, "device": {"count": 1}}
    ctx["cell"]["name"] = "no-such-profile"
    for desc, read in run.load_metrics(CELL):
        if desc["name"] in OWN | SHARED:
            assert read(ctx) is None, desc["name"]
    ctx["cell"] = run.load_cell(CELL)
    ctx["cell"]["name"] = "no-such-profile"
    for desc, read in run.load_metrics(CELL):
        if desc["name"] in OWN | SHARED:
            assert read(ctx) is None, desc["name"]


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


# ------------------------------------------------- the tiny cell on the CPU
@pytest.fixture(scope="module")
def keye_copy(tmp_path_factory):
    """A copy of benchmark/ with the tiny Keye cell added."""
    dst = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "keye"), dst,
                    dirs_exist_ok=True)
    (dst / "workloads" / "tiny-keye-train.json").write_text(json.dumps({
        "config": "tiny-keye", "traffic": "tiny-seq16-b2-keye", "chips": 1,
        "why": "throw-away cell of the tests", "limits": TINY_LIMITS}))
    for name in OWN | SHARED:
        path = dst / "metrics" / f"{name}.json"
        desc = json.loads(path.read_text())
        desc["workloads"].append("tiny-keye-train")
        path.write_text(json.dumps(desc))
    return str(dst)


def test_tiny_cell_runs_and_is_correct_on_the_cpu(keye_copy):
    out, err = drive(keye_copy, "tiny-keye-train", 2147483659)
    assert KEYS <= set(out) and out["correct"] is True, err[-2000:]
    assert "compiles inside the window: 0 backend" in err
    line = next(l for l in err.splitlines() if "rows routed" in l)
    prog, ref = line.split("program ")[1].split(", reference ")
    assert [n for _, n in eval(prog)] == eval(ref)


def test_traced_tiny_cell_reads_the_unselected_work_counter(keye_copy):
    """On the CPU the XLA math computes every block's whole square of 256
    entries a head; the selection keeps 70."""
    out, _ = drive(keye_copy, "tiny-keye-train", 7, trace=1)
    m = out["metrics"]
    assert m["attn.unselected_work_pct"]["value"] == pytest.approx(
        100 * (1 - 70 / 256))
    assert not {"kernel.sparse_core_roofline", "kernel.indexer_roofline",
                "step.attn_select_ms"} & set(m)


def drive_keye_fault(bench, cell, fault):
    """One run with ``fault`` planted under the cell's own driver. The
    executable store is off: its key holds the configuration, not the code,
    and ``dense_core`` changes the code alone."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_keye_faults.py"), bench,
         cell, "2147483659", "0.5", fault],
        env=dict(os.environ, JAX_PLATFORMS="cpu", DL4J_COMPILE_CACHE="0"),
        cwd=os.path.dirname(bench), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.parametrize("fault", ["dense_core", "topk_halved",
                                   "no_index_loss", "no_renorm", "half_batch"])
def test_each_planted_fault_is_not_correct(keye_copy, fault):
    assert_not_correct(*drive_keye_fault(keye_copy, "tiny-keye-train", fault))


def test_state_left_unchanged_is_not_correct(keye_copy):
    assert_not_correct(*drive_fault(keye_copy, "tiny-keye-train",
                                    "state_unchanged"))


def test_half_repeated_halves_a_single_sequence():
    import numpy as np

    import keye_faults
    from deeplearning4j_tpu.datasets.dataset import DataSet

    one = DataSet(np.arange(8, dtype=np.int32)[None],
                  np.arange(1, 9, dtype=np.int32)[None])
    two = DataSet(np.arange(8, dtype=np.int32).reshape(2, 4),
                  np.arange(8, dtype=np.int32).reshape(2, 4) + 1)
    a, b = keye_faults.half_repeated([one, two])
    assert a.features.tolist() == [[0, 1, 2, 3, 0, 1, 2, 3]]
    assert a.labels.tolist() == [[1, 2, 3, 4, 1, 2, 3, 4]]
    assert b.features.tolist() == [[0, 1, 2, 3], [0, 1, 2, 3]]
    assert one.features.tolist() == [list(range(8))]       # a copy
