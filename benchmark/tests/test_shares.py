"""Shares of a roofline or of the peak: ``kernel.dense_roofline`` on a
made-up ``ctx`` (read by scope, not by who runs the operation), the operands
a share keeps, and ``run.py``'s line on standard error where one reads over
100."""
import json

import pytest

import flops
import run
import scope_reduce
from costs import Share

CELL = "deepseek-v2-lite-ep8-train-seq4096"
PEAK = {"bf16_flops_per_s": 197e12}
BLOCK = "jit(dl4j_train_ksteps)/while/body/closed_call/jvp(layer/3_DecoderBlock)"
BACK = ("jit(dl4j_train_ksteps)/while/body/closed_call/transpose(jvp(layer/"
        "3_DecoderBlock))/jvp(layer/3_DecoderBlock)/checkpoint")


def reader(name):
    return next(read for d, read in run.load_metrics(CELL)
                if d["name"] == name)


@pytest.fixture()
def ctx(monkeypatch):
    """The DeepSeek cell with a made-up table of device events
    (``scope_reduce._events``'s ``[(op_name, ms a step)]``)."""
    events = []
    monkeypatch.setattr(scope_reduce, "_events", lambda ctx: events)
    cell = run.load_cell(CELL)
    return {"cell": cell, "peak": PEAK, "device": {"count": 1},
            "events": events}


def dense_need_s(cell) -> float:
    """Seconds at the peak of the step's untagged products."""
    by_scope = flops.train_flops_by_scope(cell["config"])
    assert set(by_scope) == {None, "attn/core", "moe/experts"}
    assert sum(by_scope.values()) == flops.train_flops_of(cell["config"])
    return by_scope[None] * cell["traffic"]["batch"] / PEAK["bf16_flops_per_s"]


def test_dense_roofline_reads_by_scope(ctx):
    need_ms = 1e3 * dense_need_s(ctx["cell"])
    ctx["events"] += [
        (BLOCK + "/attn/dot_general", 40.0),
        (BACK + "/attn/dot_general", 60.0),
        # a Pallas custom call under attn (a projection moved out of XLA)
        # is in the denominator: the scope says whose time it is
        (BLOCK + "/attn/pallas_call", 20.0),
        (BACK + "/rematted_computation/attn/mul", 5.0),      # a norm: time
        (BLOCK + "/ffn/dot_general", 30.0),
        (BLOCK + "/moe/shared/dot_general", 25.0),
        (BLOCK + "/moe/router/dot_general", 5.0),
        ("jit(dl4j_train_ksteps)/while/body/closed_call/transpose(jvp(loss))"
         "/dot_general", 15.0),
        # the tagged scopes' time is out, and so is what is no product's
        (BLOCK + "/attn/core/pallas_call", 70.0),
        (BACK + "/attn/core/pallas_call", 90.0),
        (BLOCK + "/moe/experts/pallas_call", 50.0),
        (BLOCK + "/moe/dispatch/gather", 12.0),
        ("jit(dl4j_train_ksteps)/while/body/closed_call/update/sub", 13.0),
        ("", 21.0)]
    value = reader("kernel.dense_roofline")(ctx)
    assert value == pytest.approx(100 * need_ms / 200.0)
    assert value.operands == pytest.approx(
        {"least_s": need_ms / 1e3, "device_s": 0.2})
    # on four chips the events are a chip's mean and the peak is four chips'
    ctx["device"] = {"count": 4}
    assert reader("kernel.dense_roofline")(ctx) == pytest.approx(value / 4)


def test_dense_roofline_cannot_pass_100_at_the_peak(ctx):
    """Every required product run exactly at the peak, whoever runs it, and
    nothing else under the scopes: 100; any norm or recomputation more."""
    need_ms = 1e3 * dense_need_s(ctx["cell"])
    ctx["events"] += [(BLOCK + "/attn/pallas_call", 0.25 * need_ms),
                      (BLOCK + "/ffn/dot_general", 0.75 * need_ms),
                      (BLOCK + "/attn/core/pallas_call", 1.0)]
    assert reader("kernel.dense_roofline")(ctx) == pytest.approx(100.0)
    ctx["events"].append((BACK + "/rematted_computation/ffn/dot_general", 1.0))
    assert reader("kernel.dense_roofline")(ctx) < 100.0


def test_dense_roofline_is_silent_where_there_is_nothing_to_read(ctx):
    assert reader("kernel.dense_roofline")(ctx) is None      # no events
    ctx["events"].append((BLOCK + "/attn/core/pallas_call", 70.0))
    assert reader("kernel.dense_roofline")(ctx) is None      # none dense
    # a reference that tags nothing: every product is XLA's, and
    # kernel.matmul_roofline is the cell's metric
    ctx["cell"] = run.load_cell("resnet50-train-b128")
    ctx["events"].append(("jit(f)/jvp(layer/stem)/attn/conv", 9.0))
    assert reader("kernel.dense_roofline")(ctx) is None


def test_a_share_keeps_its_operands_and_prints_as_a_number():
    s = Share(least_s=0.131, device_s=0.242)
    assert s == pytest.approx(54.132231)
    assert list(s.operands) == ["least_s", "device_s"]
    assert json.loads(json.dumps({"value": s})) == {"value": float(s)}


def test_a_share_over_100_is_named_with_its_operands(capsys):
    desc = lambda name, unit="%": {"name": name, "unit": unit}
    readers = [
        (desc("kernel.x_roofline"), lambda c: Share(least_s=1.26, x_s=1.0)),
        (desc("step.mfu"), lambda c: 101.0),
        (desc("kernel.y_roofline"), lambda c: Share(least_s=0.5, y_s=1.0)),
        (desc("fit.staging_wait_pct"), lambda c: 150.0),    # no such share
        (desc("step.device_ms", "ms"), lambda c: 500.0),
        (desc("kernel.z_roofline"), lambda c: None)]
    out = run.read_metrics({}, readers)
    # the result is as it was read: nothing capped, the silent one left out
    assert {k: v["value"] for k, v in out.items()} == {
        "kernel.x_roofline": 126.0, "step.mfu": 101.0,
        "kernel.y_roofline": 50.0, "fit.staging_wait_pct": 150.0,
        "step.device_ms": 500.0}
    warned = [l for l in capsys.readouterr().err.splitlines()
              if "IMPOSSIBLE SHARE" in l]
    assert len(warned) == 2
    assert "kernel.x_roofline reads 126" in warned[0]
    assert "least_s 1.26 over x_s 1" in warned[0]
    assert "step.mfu reads 101" in warned[1] and "no operands" in warned[1]
