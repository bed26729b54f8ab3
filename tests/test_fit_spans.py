"""The training path names its work: one span per stage of every staged
K-step group in the flight recorder's ring (``input.pull/stack/cast/h2d`` on
the producer, ``fit.wait/step_wait/dispatch/listeners`` on the fit loop,
sharing the group's number, on ``time.time_ns()``'s clock), and a ``jax.named_scope`` per
layer and phase in the K-step program, whose module has a name of its own;
and what each layer says a step counts, booked a dispatched step."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph_network import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.compile_tracker import global_tracker
from deeplearning4j_tpu.observability.flight_recorder import (
    FlightRecorder, global_recorder,
)
from deeplearning4j_tpu.observability.metrics import global_registry

STAGES = ("input.pull", "input.stack", "input.cast", "input.h2d")
FIT = ("fit.wait", "fit.dispatch", "fit.listeners")
K = 4


def make_net(kind, n_in=16):
    b = NeuralNetConfiguration.builder().seed(3).learning_rate(0.05)
    dense = DenseLayer(n_in=n_in, n_out=8, activation="tanh")
    out = OutputLayer(n_in=8, n_out=3, loss="mcxent", activation="softmax")
    if kind == "multilayer":
        net = MultiLayerNetwork(b.list().layer(dense).layer(out).build())
    else:
        net = ComputationGraph(
            b.graph_builder().add_inputs("in")
            .add_layer("dense", dense, "in").add_layer("out", out, "dense")
            .set_outputs("out").build())
    net = net.init(seed=3)
    net.dispatch_ksteps = K
    return net


def batches(n, batch=8, n_in=16, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(batch, n_in)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


def spans_by_group():
    """The staged groups' own spans by group and name (a ``fit.call`` and
    the ``compile.*`` spans of a program's resolution are
    ``tests/test_startup_spans.py``'s)."""
    out = {}
    for e in global_recorder().snapshot():
        if "t0_ns" in e and e["name"] in STAGES + FIT + ("fit.step_wait",):
            out.setdefault(e["group"], {})[e["name"]] = e
    return out


def staging_seconds(path):
    fam = global_registry().snapshot().get(
        "dl4j_prefetch_staging_seconds_total", {"series": []})
    return sum(s["value"] for s in fam["series"]
               if s["labels"].get("path") == path)


@pytest.fixture
def ring():
    rec = global_recorder()
    rec.clear()
    yield rec
    rec.set_enabled(True)
    rec.clear()


def fit_through(entry, net, data, depth=2):
    """Fit ``data`` through one of the staged loop's entry points: the
    network's own ``fit_iterator``, or a synchronous ``ParallelWrapper`` over
    four devices."""
    if entry == "wrapper":
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        wrapper = (ParallelWrapper.builder(net).workers(4)
                   .prefetch_buffer(depth).build())
        wrapper.fit(ListDataSetIterator(data))
        return wrapper
    net.prefetch_depth = depth
    net.fit_iterator(ListDataSetIterator(data))
    return net


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("kind", ["multilayer", "graph"])
@pytest.mark.parametrize("entry", ["fit_iterator", "wrapper"])
def test_spans_of_a_group_share_it_and_nest_in_time(ring, entry, kind, depth):
    """One loop, one span set: the wrapper's groups carry what the networks'
    do, their dispatch under the wrapper's program name with the bytes of the
    gradient all-reduce."""
    net = make_net(kind)
    net.stage_dtype = jnp.bfloat16
    before = time.time_ns()
    fit_through(entry, net, batches(3 * K), depth)
    groups = spans_by_group()
    assert len(groups) == 3
    for i, (group, by_name) in enumerate(sorted(groups.items())):
        # the fit loop waits for the step two groups back, from the third on
        step_wait = by_name.pop("fit.step_wait", None)
        assert (step_wait is not None) == (i >= 2)
        assert set(by_name) == set(STAGES + FIT), by_name.keys()
        pull, stack, cast, h2d = (by_name[n] for n in STAGES)
        wait, dispatch, listeners = (by_name[n] for n in FIT)
        # pull <= stack <= cast <= h2d <= wait's end <= dispatch <= listeners
        edges = [before, pull["t0_ns"], pull["t1_ns"], stack["t0_ns"],
                 stack["t1_ns"], cast["t0_ns"], cast["t1_ns"], h2d["t0_ns"],
                 h2d["t1_ns"], wait["t1_ns"], dispatch["t0_ns"],
                 dispatch["t1_ns"], listeners["t0_ns"], listeners["t1_ns"],
                 time.time_ns()]
        assert edges == sorted(edges)
        assert stack["t1_ns"] == cast["t0_ns"] and cast["t1_ns"] == h2d["t0_ns"]
        assert {s["cause"] for s in (stack, cast, h2d)} == {"input.pull"}
        assert dispatch["cause"] == "fit.wait"
        assert listeners["cause"] == "fit.dispatch"
        # the step event is the dispatch span, not a second record
        assert dispatch["kind"] == "step" and dispatch["k"] == K
        assert dispatch["it"] == i * K and dispatch["batch"] == 8
        assert dispatch["dispatch_s"] == pytest.approx(
            (dispatch["t1_ns"] - dispatch["t0_ns"]) / 1e9, abs=1e-3)
        if entry == "wrapper":
            assert dispatch["path"] == "ParallelWrapper.sync_multistep"
            assert dispatch["collective_bytes"] == K * sum(
                p.nbytes for p in jax.tree_util.tree_leaves(net.params_list))
        else:
            assert dispatch["path"] == f"{type(net).__name__}.multistep"
            assert "collective_bytes" not in dispatch
        assert h2d["bytes"] == K * 8 * (16 * 2 + 3 * 4)   # bf16 in, f32 labels
        producer = "MainThread" if depth == 0 else "dl4j-prefetch-staging"
        assert {s["thread"] for s in (pull, stack, cast, h2d)} == {producer}
        assert {s["thread"] for s in (wait, dispatch, listeners)} == {
            "MainThread"}
        if step_wait:
            assert (wait["t1_ns"] <= step_wait["t0_ns"] <= step_wait["t1_ns"]
                    <= dispatch["t0_ns"])
            assert step_wait["cause"] == "fit.dispatch"
            assert step_wait["thread"] == "MainThread"
    steps = [e for e in global_recorder().snapshot() if e["kind"] == "step"]
    assert len(steps) == 3


@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_wrapper_groups_carry_the_stage_spans_and_one_step_wait(ring, kind):
    """``ParallelWrapper``'s synchronous loop dispatches under the networks'
    bound (their spans: the test above): from the third group on it waits for
    the loss stack of the group two before, and the wait is over before the
    step is dispatched. The bound reaches across ``fit`` calls on one
    wrapper."""
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = make_net(kind)
    net.stage_dtype = jnp.bfloat16
    wrapper = ParallelWrapper.builder(net).workers(4).prefetch_buffer(2).build()
    order = []
    make = ParallelWrapper._make_sync_multistep

    class Losses:
        def __init__(self, i, real):
            self.i, self.real = i, real

        def block_until_ready(self):
            order.append(("waited", self.i))

        def __getitem__(self, j):
            return self.real[j]

    def multi(params, states, upd, xs, ys, rng, it):
        i = sum(1 for o in order if o[0] == "dispatch")
        order.append(("dispatch", i))
        *state, losses = real(params, states, upd, xs, ys, rng, it)
        return (*state, Losses(i, losses))

    real = make(wrapper)
    wrapper._make_sync_multistep = lambda: multi
    wrapper.fit(ListDataSetIterator(batches(4 * K)))
    assert order == [("dispatch", 0), ("dispatch", 1), ("waited", 0),
                     ("dispatch", 2), ("waited", 1), ("dispatch", 3)]
    groups = spans_by_group()
    assert len(groups) == 4
    for i, (group, by_name) in enumerate(sorted(groups.items())):
        step_wait = by_name.pop("fit.step_wait", None)
        assert (step_wait is not None) == (i >= 2)
        assert set(by_name) == set(STAGES + FIT), by_name.keys()
        assert by_name["fit.dispatch"]["it"] == i * K
        if step_wait:
            assert (by_name["fit.wait"]["t1_ns"] <= step_wait["t0_ns"]
                    <= step_wait["t1_ns"] <= by_name["fit.dispatch"]["t0_ns"])
    # a second fit on the wrapper goes on where the first stopped
    del order[:]
    wrapper.fit(ListDataSetIterator(batches(K)))
    assert order == [("waited", 2), ("dispatch", 0)]


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_stage_spans_add_up_to_the_staging_counter(ring, kind, depth):
    # groups large enough (32 MB of float32 each) that the interpreter's
    # time between two spans is far under the 1 % asked
    net = make_net(kind, n_in=4096)
    net.prefetch_depth = depth
    net.stage_dtype = jnp.bfloat16
    data = batches(3 * K, batch=512, n_in=4096)
    before = staging_seconds(kind)
    net.fit_iterator(ListDataSetIterator(data))
    counted = staging_seconds(kind) - before
    spans = sum(e["t1_ns"] - e["t0_ns"] for e in global_recorder().snapshot()
                if e.get("name") in STAGES) / 1e9
    assert counted > 0.01
    assert spans == pytest.approx(counted, rel=0.01)
    # every group still has its three stage spans, none empty, one after the
    # other: taking a slot and writing the labels, the pass over the
    # features, the submission (a reader of a span that is absent reads null)
    groups = spans_by_group()
    assert len(groups) == 3
    for by_name in groups.values():
        stack, cast, h2d = (by_name[n] for n in STAGES[1:])
        assert stack["t0_ns"] < stack["t1_ns"] == cast["t0_ns"]
        assert cast["t0_ns"] < cast["t1_ns"] == h2d["t0_ns"] < h2d["t1_ns"]
        # one pass: the features take longer than the slot and the labels
        assert cast["t1_ns"] - cast["t0_ns"] > stack["t1_ns"] - stack["t0_ns"]


@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_recorder_disabled_records_no_span_and_trains_the_same(ring, kind):
    def fit(enabled):
        ring.set_enabled(enabled)
        ring.clear()
        net = make_net(kind)
        net.fit_iterator(ListDataSetIterator(batches(2 * K)))
        return ([np.asarray(p) for p in
                 jax.tree_util.tree_leaves(net.params_list)], len(ring))

    on, n_on = fit(True)
    off, n_off = fit(False)
    assert n_on >= 2 * len(STAGES + FIT) and n_off == 0
    for a, b in zip(on, off):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("kind,layer", [("multilayer", "layer/0_DenseLayer"),
                                        ("graph", "layer/dense")])
def test_kstep_program_is_named_and_scoped(kind, layer):
    net = make_net(kind)
    net.fit_iterator(ListDataSetIterator(batches(K)))
    exe = global_tracker().executable(f"{type(net).__name__}.multistep")
    text = exe.as_text()
    assert text.startswith("HloModule jit_dl4j_train_ksteps")
    for op_name in (f"jvp({layer})/", "jvp(loss)/", "/update/",
                    f"transpose(jvp({layer}))/"):
        assert op_name in text, op_name


class _Counting(DenseLayer):
    """Not a decoder block: a layer that says what its step counts."""

    def step_counts(self, batch, seq, dtype):
        return {"dl4j_test_rows_total": batch * seq,
                "dl4j_test_steps_total": 1}


def test_the_loop_books_whatever_counts_a_layer_reports():
    """The fit loop books each layer's ``step_counts`` a dispatched step,
    under the layer's index, knowing no kind of layer: here a dense layer's
    rows and steps over 3 groups of K steps, and nothing for the output
    layer, which reports none."""
    b = NeuralNetConfiguration.builder().seed(3).learning_rate(0.05)
    net = MultiLayerNetwork(b.list().layer(
        _Counting(n_in=16, n_out=8, activation="tanh")).layer(
        OutputLayer(n_in=8, n_out=3, loss="mcxent", activation="softmax"))
        .build()).init(seed=3)
    net.dispatch_ksteps = K

    def booked():
        snap = global_registry().snapshot()
        return {(name, s["labels"]["layer"]): s["value"]
                for name in ("dl4j_test_rows_total", "dl4j_test_steps_total")
                for s in snap.get(name, {}).get("series", ())}

    before = booked()
    net.fit_iterator(ListDataSetIterator(batches(3 * K)))
    after = booked()
    assert {k: v - before.get(k, 0) for k, v in after.items()} == {
        ("dl4j_test_rows_total", "0"): 3 * K * 8 * 16,
        ("dl4j_test_steps_total", "0"): 3 * K}


def test_scopes_stay_inside_a_config_declared_policy():
    # wrap_with_policy keeps the function's name, so the module is not
    # "jit_wrapped" under a dtype policy either (both cells run under one)
    net = make_net("multilayer")
    net.conf.global_conf.dtype = "bfloat16_full"
    net.fit_iterator(ListDataSetIterator(batches(K)))
    text = global_tracker().executable(
        "MultiLayerNetwork.multistep").as_text()
    assert text.startswith("HloModule jit_dl4j_train_ksteps")


def test_record_span_fields_and_kill_switch():
    rec = FlightRecorder(capacity=4)
    rec.record_span("input.cast", 10, 30, group=7, cause="input.pull")
    (ev,) = rec.snapshot()
    assert ev == {"kind": "span", "ts": 30 * 1e-9, "name": "input.cast",
                  "t0_ns": 10, "t1_ns": 30, "thread": "MainThread",
                  "group": 7, "cause": "input.pull"}
    rec.set_enabled(False)
    rec.record_span("input.cast", 10, 30)
    assert len(rec) == 1


def test_span_exit_carries_the_interval_and_series_is_resolved_once():
    from deeplearning4j_tpu.observability import MetricsRegistry, span

    rec, reg = FlightRecorder(capacity=8), MetricsRegistry()
    lookups = []
    histogram = reg.histogram
    reg.histogram = lambda *a, **kw: lookups.append(a) or histogram(*a, **kw)
    for _ in range(3):
        t0 = time.time_ns()
        with span("epoch/0/fwd", metric_name="epoch", registry=reg,
                  recorder=rec):
            pass
    exit_ev = rec.snapshot()[-1]
    assert exit_ev["kind"] == "span_exit"
    assert t0 <= exit_ev["t0_ns"] <= exit_ev["t1_ns"] <= time.time_ns()
    assert len(lookups) == 1
    series = reg.snapshot()["dl4j_span_seconds"]["series"]
    assert [(s["labels"], s["count"]) for s in series] == [
        ({"name": "epoch"}, 3)]


def test_program_rev_is_in_the_executable_stores_key(monkeypatch):
    # scope names are op metadata, in neither the store's key nor JAX's: an
    # entry written before a change of scopes must not be loaded after it
    from deeplearning4j_tpu.nn import compile_cache

    prog = compile_cache.CachedProgram("t.f", jax.jit(lambda x: x))
    sig = (("f32[4]",), ())
    before = prog._fp_hex(sig)
    assert before == prog._fp_hex(sig)
    monkeypatch.setattr(compile_cache, "PROGRAM_REV", "another")
    assert prog._fp_hex(sig) != before
