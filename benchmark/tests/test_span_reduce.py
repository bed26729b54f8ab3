"""span_reduce.py on a small synthetic profile, span list and module text:
the clock mapping, the launch pairing, the idle attribution and the fusion
rule."""
import os

import pytest
from jax.profiler import ProfileData

import span_reduce
import trace_reduce
from conftest import HERE

T0 = 10**18          # the profile's profile_start_time
MS = 10**6


def span(name, t0_ms, t1_ms, group, **fields):
    """A ring record at ``t0_ms..t1_ms`` of the profile's own clock."""
    return dict(name=name, t0_ns=T0 + round(t0_ms * MS),
                t1_ns=T0 + round(t1_ms * MS), group=group, **fields)


def group_spans(g, producer_group=None):
    """One staged group on the trace's 10 ms period: staged while execution
    ``g - 1`` runs and the device then idles, dispatched 0.2 ms before its
    own execution starts at ``10 g + 1`` ms."""
    p, q = 10 * (g - 1), g if producer_group is None else producer_group
    return [span("input.pull", p + 1.2, p + 2, q),
            span("input.stack", p + 2, p + 4, q, cause="input.pull"),
            span("input.cast", p + 4, p + 10, q, cause="input.pull"),
            span("input.h2d", p + 10, p + 10.5, q, cause="input.pull"),
            span("fit.wait", p + 1.1, p + 10.6, g),
            span("fit.dispatch", p + 10.8, p + 10.9, g, kind="step", k=4,
                 cause="fit.wait"),
            span("fit.listeners", p + 10.9, p + 11, g, cause="fit.dispatch")]


@pytest.fixture(scope="module")
def profile():
    with open(os.path.join(HERE, "data", "span_trace.textproto")) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def module_text():
    with open(os.path.join(HERE, "data", "span_module.txt")) as f:
        return f.read()


def reduced(profile, module_text, spans):
    trace = trace_reduce.reduce_profile(profile)
    return span_reduce.reduce_profile(profile, spans, module_text, trace, 4)


def test_clock_mapping_and_slice(profile, module_text):
    spans = [s for g in (1, 2, 3) for s in group_spans(g)]
    r = reduced(profile, module_text, spans)
    # trace_reduce's slice, the second start to the last (11 ms .. 31 ms of
    # the profile's clock), as ns since the epoch
    assert (r["lo_ns"], r["hi_ns"]) == (T0 + 11 * MS, T0 + 31 * MS)
    assert r["steps"] == 8
    # the cast spans of the slice start 5 us before their _stage_host frames
    assert r["clock_check"] == {"spans": 2, "max_apart_ns": 5000}


def test_slice_that_cannot_be_found_again_is_an_error(profile, module_text):
    trace = dict(trace_reduce.reduce_profile(profile), window_s=0.0123)
    with pytest.raises(ValueError, match="cannot find trace_reduce's slice"):
        span_reduce.reduce_profile(profile, [], module_text, trace, 4)


def test_fusion_rule(module_text):
    where = span_reduce.classify_module(module_text)
    conv = "3_ConvolutionLayer"
    assert where["fusion.1"] == ("forward", conv)
    # a convolution under transpose(jvp(layer/3)) fused into a root under
    # update is backward
    assert where["subtract_subtract_fusion.2"] == ("backward", conv)
    # no product: the root's phase; the gradient's conversion is its input
    assert where["subtract_subtract_fusion.3"] == ("update", "update")
    # a root tuple whose outputs lie in two phases
    assert where["fusion.4"][0] == "mixed"
    assert where["dynamic-slice.1"] == ("unscoped", "")
    assert span_reduce.classify_module(
        module_text.replace("layer/", "l/").replace("update", "u")
        .replace("loss", "l")) == {}


def test_device_time_by_phase(profile, module_text):
    r = reduced(profile, module_text, [])
    # two periods of 2 + 3 + 1 ms over 8 steps; the other program's
    # operation carries the name of a forward fusion and is not one
    assert r["phase_ms"] == pytest.approx({
        "forward": 0.5, "backward": 0.75, "update": 0.25, "mixed": 0.0,
        "unscoped": 0.125})
    trace = trace_reduce.reduce_profile(profile)
    assert sum(r["phase_ms"].values()) == pytest.approx(
        1e3 * trace["busy_s"] / 8)
    assert r["by_layer_ms"]["backward", "3_ConvolutionLayer"] == \
        pytest.approx(0.75)
    # a module without scopes (an older program): nothing to read
    old = module_text.replace("layer/", "l/").replace("update", "u")
    assert span_reduce.reduce_profile(
        profile, [], old, trace, 4)["phase_ms"] is None


def test_launch_pairing(profile, module_text):
    spans = [s for g in (1, 2, 3) for s in group_spans(g)]
    r = reduced(profile, module_text, spans)
    assert r["launch_ms"] == pytest.approx([0.2, 0.2])
    # a queue of dispatches: the n-th pairs with the n-th execution, anchored
    # where the device waited (the execution at 96), so a start is measured
    # from the previous end where that is later than the host's call
    executions = [(0, 90), (96, 190), (191, 290), (292, 390)]
    calls = [(95, 95.5), (97, 97.5), (99, 99.5)]
    assert [start - since for (since, start), _
            in span_reduce.launches(executions, calls)] == [1, 1, 2]
    # ... and from the call's return, what the host no longer waited for
    assert [p for _, p in span_reduce.launches(executions, calls)] == [
        (95.5, 96), (190, 191), (290, 292)]
    assert span_reduce.launches(executions, []) == []
    # a device start before the host call that caused it reads negative
    early = [dict(s, t0_ns=s["t0_ns"] + MS) if s["name"] == "fit.dispatch"
             else s for s in spans]
    r = reduced(profile, module_text, early)
    assert min(r["launch_ms"]) == pytest.approx(-0.8)


def test_idle_attribution(profile, module_text, monkeypatch):
    spans = [s for g in (1, 2, 3) for s in group_spans(g)]
    r = reduced(profile, module_text, spans)
    # idle: 18..21 and 27..31 ms (17..18 is the other program's). The
    # dispatch returns 0.1 ms before its execution starts: that is the
    # launch's, though fit.listeners covers it
    assert r["idle_s"] == pytest.approx(7e-3)
    assert r["idle_by_owner_s"] == pytest.approx({
        "input.cast": 5e-3, "input.h2d": 1e-3, "fit.wait (bare)": 0.2e-3,
        "no span": 0.4e-3, "fit.dispatch": 0.2e-3, "fit.launch": 0.2e-3})
    assert "fit.listeners" not in r["idle_by_owner_s"]
    span_reduce._memo["x"] = r
    monkeypatch.setattr(span_reduce, "find_xplane", lambda cell: "x")
    assert span_reduce.idle_attributed_pct({"cell": {"name": "-"}}) == \
        pytest.approx(100 * 6.4 / 7)
    del span_reduce._memo["x"]
    # the producer staging another group than the one waited for: a gap
    # under a bare wait is not attributed
    other = group_spans(1) + group_spans(2) + group_spans(3, producer_group=4)
    r = reduced(profile, module_text, other)
    assert r["idle_by_owner_s"]["fit.wait (bare)"] == pytest.approx(3.7e-3)
    assert r["idle_by_owner_s"]["input.cast"] == pytest.approx(2e-3)
    # no spans at all (an older program): nothing to read
    r = reduced(profile, module_text, [])
    assert r["idle_by_owner_s"] == {} and r["launch_ms"] == []


def test_readers_over_the_window(monkeypatch):
    spans = [s for g in (1, 2, 3) for s in group_spans(g)]
    monkeypatch.setattr(span_reduce, "program_spans", lambda: spans)
    ctx = {"window": {"steps": 8, "dispatches": 2}}
    # the last two dispatched groups are the window's: 6 ms of cast each
    assert span_reduce.window_groups(spans, 2) == {2, 3}
    assert span_reduce.stage_ms_per_batch(ctx, "input.cast") == \
        pytest.approx(12 / 8)
    assert span_reduce.stage_ms_per_batch(ctx, "input.stack") == \
        pytest.approx(4 / 8)
    monkeypatch.setattr(span_reduce, "program_spans", lambda: [])
    assert span_reduce.stage_ms_per_batch(ctx, "input.cast") is None
