"""The program times its own start: from the OS's start of the process to the
return of the first ``fit_iterator``, one span each in the flight recorder's
ring (``observability/startup.py``, ``nn/compile_cache.py``,
``nn/multilayer.py::book_fit_call``), written at its end and never on the
per-dispatch path."""
import builtins
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.datasets.prefetch import current_group
from deeplearning4j_tpu.nn import compile_cache as cc
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph_network import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import startup
from deeplearning4j_tpu.observability.compile_tracker import CompileTracker
from deeplearning4j_tpu.observability.flight_recorder import (
    FlightRecorder, global_recorder,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 2

#: a process of its own: the package's import, ``init()`` and one
#: ``fit_iterator`` of a tiny network, then the ring as JSON
SCRIPT = textwrap.dedent("""
    import json, logging, sys
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    import numpy as np
    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.observability.flight_recorder import (
        global_recorder)
    from deeplearning4j_tpu.observability.startup import time_to_first_step

    conf = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.1)
            .list().layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax")).build())
    net = MultiLayerNetwork(conf).init()
    net.dispatch_ksteps = 2
    x = np.ones((8, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[np.arange(8) % 3]
    net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 4))
    net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 2))
    print(json.dumps({"events": global_recorder().snapshot(),
                      "rows": time_to_first_step()}, default=repr))
""")


@pytest.fixture(scope="module")
def started(tmp_path_factory):
    """``SCRIPT``'s ring, rows and standard error, from a cold store."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("startup_xcache")))
    t0_ns = time.time_ns()
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.splitlines()[-1])
    spans = [e for e in result["events"] if "t0_ns" in e]
    return {"spans": spans, "rows": result["rows"], "stderr": out.stderr,
            "launched_ns": t0_ns, "returned_ns": time.time_ns()}


def one(spans, name, **fields):
    """The first span called ``name`` whose fields are ``fields``."""
    return next(s for s in spans if s["name"] == name
                and all(s.get(k) == v for k, v in fields.items()))


def within(span, outer):
    return outer["t0_ns"] <= span["t0_ns"] and span["t1_ns"] <= outer["t1_ns"]


def test_the_ring_holds_the_start_in_order(started):
    spans = started["spans"]
    assert all(s["t0_ns"] <= s["t1_ns"] for s in spans)
    # a record is written at its span's end: the roots follow one another,
    # a parent follows its children
    names = [s["name"] for s in spans]
    order = ["startup.before_import", "startup.import", "startup.init",
             "input.h2d", "compile.lower", "compile.backend",
             "compile.store_write", "compile.resolve", "fit.dispatch",
             "fit.listeners", "fit.call"]
    assert [n for n in dict.fromkeys(names) if n in order] == order
    before, imported = one(spans, "startup.before_import"), one(
        spans, "startup.import")
    # the OS's start of the process lies between the test's launch of it
    # and the package's first line, to the clock tick /proc counts in
    assert started["launched_ns"] - 20_000_000 <= before["t0_ns"]
    assert before["t1_ns"] == imported["t0_ns"]
    assert before["argv0"] == "-c" and "cause" not in before
    assert spans[-1]["t1_ns"] <= started["returned_ns"]
    # one record a span: both calls wrote a fit.call, one program resolved
    assert names.count("fit.call") == 2
    assert names.count("startup.import") == names.count("compile.resolve") == 1


#: table A on a cold store: span -> (its cause, the span it lies inside)
TREE = {
    "startup.import": ("startup.before_import", None),
    "startup.init": ("startup.import", None),
    "fit.call": (None, None),
    "input.h2d": ("input.pull", "fit.call"),
    "fit.dispatch": ("fit.wait", "fit.call"),
    "fit.listeners": ("fit.dispatch", "fit.call"),
    "compile.resolve": ("fit.dispatch", "fit.dispatch"),
    "compile.lower": ("compile.resolve", "compile.resolve"),
    "compile.backend": ("compile.resolve", "compile.resolve"),
    "compile.store_write": ("compile.resolve", "compile.resolve"),
}


@pytest.mark.parametrize("name", sorted(TREE))
def test_a_span_names_its_cause_and_lies_inside_its_parent(started, name):
    spans = started["spans"]
    cause, parent = TREE[name]
    span = one(spans, name)
    assert span.get("cause") == cause
    if parent is not None:
        assert within(span, one(spans, parent))
    if name.startswith("compile."):
        # a resolution inside a dispatch is that dispatch's group's
        assert span["group"] == one(spans, "fit.dispatch")["group"]


def test_the_first_tree_is_whole_and_its_fields_say_what_ran(started):
    spans = started["spans"]
    imported, init, call = (one(spans, n) for n in (
        "startup.import", "startup.init", "fit.call"))
    assert imported["t1_ns"] <= init["t0_ns"] <= init["t1_ns"] <= call["t0_ns"]
    assert (init["cls"], init["params"]) == (
        "MultiLayerNetwork", 4 * 8 + 8 + 8 * 3 + 3)
    assert (call["path"], call["k"], call["epochs"]) == ("multilayer", 2, 1)
    resolve = one(spans, "compile.resolve")
    # the ring's compile record, now with an interval
    assert resolve["kind"] == "compile" and resolve["hit"] is False
    assert resolve["fn"] == "MultiLayerNetwork.multistep"
    assert resolve["cache_hit"] is False and resolve["wall_s"] > 0
    assert resolve["payload_bytes"] > 0
    assert one(spans, "compile.store_write")["bytes"] > 0
    for figure in ("temp_bytes", "argument_bytes", "output_bytes",
                   "alias_bytes", "code_bytes"):
        assert isinstance(resolve[figure], int) and resolve[figure] >= 0
    assert not [e for e in spans if e["kind"] == "compile"
                and "t0_ns" not in e]


def test_a_later_dispatch_and_a_later_call_resolve_nothing(started):
    spans = started["spans"]
    first, second = [s for s in spans if s["name"] == "fit.call"]
    later = [s for s in spans if s["t0_ns"] >= first["t1_ns"]]
    # the second call's one group: seven spans and the call's own
    assert sorted(s["name"] for s in later) == sorted([
        "input.pull", "input.stack", "input.cast", "input.h2d", "fit.wait",
        "fit.dispatch", "fit.listeners", "fit.call"])
    dispatches = [s for s in spans if s["name"] == "fit.dispatch"]
    assert len(dispatches) == 3
    for d in dispatches[1:]:
        assert not [s for s in spans if s["name"].startswith("compile.")
                    and within(s, d)]


def test_the_operator_gets_one_line_and_a_script_the_same_rows(started):
    lines = [l for l in started["stderr"].splitlines()
             if "time to first step" in l]
    assert len(lines) == 1
    for part in ("before import", "import", "init", "first group staged",
                 "program MultiLayerNetwork.multistep compiled in",
                 "lower", "backend", "store write", "first 2 steps"):
        assert part in lines[0]
    rows = started["rows"]
    assert [r["row"] for r in rows] == [
        "total", "before_import", "import", "init", "first_group_staged",
        "program", "first_steps", "other"]
    by = {r["row"]: r for r in rows}
    spans = started["spans"]
    assert by["total"]["s"] == pytest.approx(
        (one(spans, "fit.call")["t1_ns"]
         - one(spans, "startup.before_import")["t0_ns"]) / 1e9)
    assert sum(r["s"] for r in rows[1:]) == pytest.approx(by["total"]["s"])
    assert by["other"]["s"] >= 0
    assert set(by["program"]["parts"]) == {"lower", "backend", "store_write"}
    assert sum(by["program"]["parts"].values()) <= by["program"]["s"]


# ----------------------------------------------------------- in this process
@pytest.fixture
def ring():
    rec = global_recorder()
    rec.clear()
    yield rec
    rec.set_enabled(True)
    rec.clear()


def tiny(kind="multilayer"):
    b = NeuralNetConfiguration.builder().seed(3).learning_rate(0.05)
    dense = DenseLayer(n_in=6, n_out=5, activation="tanh")
    out = OutputLayer(n_in=5, n_out=3, loss="mcxent", activation="softmax")
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


def data(n, batch=8):
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(batch, 6)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


def compile_names(rec):
    return [e["name"] for e in rec.snapshot()
            if e.get("name", "").startswith("compile.")]


@pytest.mark.parametrize("which", ["fresh", "second"])
def test_a_resolution_writes_the_parts_it_ran(ring, which):
    """A fresh program lowers, compiles and writes the store; a second
    ``CachedProgram`` over the same store reads and deserializes."""
    x = np.ones((4, 4), np.float32)

    def program():
        return cc.CachedProgram("startup_probe", jax.jit(lambda a: a @ a),
                                tracker=CompileTracker())

    first = program()
    first(x)
    if which == "second":
        ring.clear()
        program()(x)
    parts = {"fresh": ["compile.lower", "compile.backend",
                       "compile.store_write", "compile.resolve"],
             "second": ["compile.store_read", "compile.deserialize",
                        "compile.resolve"]}[which]
    assert compile_names(ring) == parts
    spans = ring.snapshot()
    resolve = one(spans, "compile.resolve")
    assert resolve["hit"] is (which == "second")
    assert resolve["kind"] == "compile" and resolve["fn"] == "startup_probe"
    # outside a fit loop the resolution has no cause
    assert resolve["cause"] is None and resolve["group"] is None
    for child in spans[:-1]:
        assert child["cause"] == "compile.resolve" and within(child, resolve)
    entry = os.path.getsize(os.path.join(cc.cache_dir(), os.listdir(
        cc.cache_dir())[0]))
    moved = one(spans, "compile.store_read" if which == "second"
                else "compile.store_write")
    assert moved["bytes"] == entry
    assert resolve["payload_bytes"] >= entry     # an entry is compressed


def test_the_second_dispatch_of_a_program_writes_no_compile_record(ring):
    program = cc.CachedProgram("startup_probe_twice", jax.jit(lambda a: a + 1),
                               tracker=CompileTracker())
    x = np.ones((4,), np.float32)
    program(x)
    n = len(ring)
    assert compile_names(ring)[-1] == "compile.resolve"
    program(x)
    program.warm(x)
    assert len(ring) == n
    program(np.ones((5,), np.float32))      # another signature: its own
    assert compile_names(ring).count("compile.resolve") == 2


@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_init_and_fit_call_of_both_networks(ring, kind):
    net = tiny(kind)
    net.fit_iterator(ListDataSetIterator(data(2 * K)), epochs=2)
    spans = ring.snapshot()
    init, call = one(spans, "startup.init"), one(spans, "fit.call")
    assert init["cls"] == type(net).__name__
    assert init["params"] == net.num_params() == 6 * 5 + 5 + 5 * 3 + 3
    assert (call["path"], call["k"], call["epochs"]) == (kind, K, 2)
    assert [s["name"] for s in spans].count("fit.call") == 1
    groups = [s for s in spans if s["name"] == "fit.dispatch"]
    assert len(groups) == 4 and all(within(s, call) for s in groups)
    resolve = one(spans, "compile.resolve")
    assert resolve["fn"] == f"{type(net).__name__}.multistep"
    assert resolve["cause"] == "fit.dispatch"
    assert resolve["group"] == groups[0]["group"] and within(
        resolve, groups[0])


def test_init_under_jit_times_the_trace(ring):
    """As the benchmark builds a network: ``init()`` inside a jitted call."""
    def shell():
        n = tiny()
        return n.state_list, n.updater_state

    jax.jit(shell)()
    init = one(ring.snapshot(), "startup.init")
    assert init["params"] == 6 * 5 + 5 + 5 * 3 + 3


def test_the_wrappers_fit_is_a_call_and_its_program_a_resolution(ring):
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = tiny()
    wrapper = ParallelWrapper.builder(net).workers(4).build()
    wrapper.fit(ListDataSetIterator(data(2 * K)))
    spans = ring.snapshot()
    call = one(spans, "fit.call")
    assert (call["path"], call["k"], call["epochs"]) == ("wrapper_sync", K, 1)
    assert [s["name"] for s in spans].count("fit.call") == 1
    resolve = one(spans, "compile.resolve",
                  fn="ParallelWrapper.sync_multistep")
    dispatch = one(spans, "fit.dispatch")
    assert resolve["cause"] == "fit.dispatch" and within(resolve, dispatch)
    assert within(dispatch, call)


def test_the_threads_group_ends_with_the_call(ring):
    """What the thread resolves after a fit call is not booked to the
    call's last dispatch."""
    net = tiny()
    net.fit_iterator(ListDataSetIterator(data(K)))
    assert current_group() is None
    ring.clear()
    net.output(np.ones((2, 6), np.float32))
    resolve = one(ring.snapshot(), "compile.resolve")
    assert resolve["cause"] is None and resolve["group"] is None


def test_a_failed_call_still_writes_its_span(ring):
    net = tiny()

    def broken():
        yield from data(1)
        raise OSError("the source broke")

    with pytest.raises(OSError):
        net.fit_iterator(broken())
    assert one(ring.snapshot(), "fit.call")["path"] == "multilayer"
    assert current_group() is None


def test_with_the_recorder_off_nothing_is_written_and_nothing_raises(ring):
    ring.set_enabled(False)
    startup.record_import(time.time_ns())
    net = tiny()
    net.fit_iterator(ListDataSetIterator(data(2 * K)))
    assert np.isfinite(float(net.score_value))
    assert len(ring) == 0
    assert startup.time_to_first_step() is None


def test_a_compile_outside_the_store_keeps_the_bare_event(ring, monkeypatch):
    """The kill switch's ``tracker.wrap`` path takes no interval: its
    ``compile`` record stays the event it was."""
    monkeypatch.setenv("DL4J_COMPILE_CACHE", "0")
    program = cc.build_program("startup_probe_plain", jax.jit(lambda a: a * 2),
                               tracker=CompileTracker())
    program(np.ones((3,), np.float32))
    (event,) = [e for e in ring.snapshot() if e["kind"] == "compile"]
    assert event["fn"] == "startup_probe_plain" and "t0_ns" not in event
    assert compile_names(ring) == []


# --------------------------------------------------- the process's own start
def test_process_start_is_this_processes(ring):
    start = startup.process_start_ns()
    assert start is not None
    assert 0 < time.time_ns() - start < 6 * 3600 * 10**9
    private = FlightRecorder(capacity=8)
    t0_ns = time.time_ns()
    startup.record_import(t0_ns, private)
    before, imported = private.snapshot()
    assert (before["name"], imported["name"]) == (
        "startup.before_import", "startup.import")
    # to the clock tick: two readings of one start
    assert abs(before["t0_ns"] - start) < 50_000_000
    assert before["t1_ns"] == imported["t0_ns"] == t0_ns
    assert imported["cause"] == "startup.before_import"
    assert len(ring) == 0                       # the private ring alone


@pytest.mark.parametrize("fault", ["no_proc", "garbled", "after_the_import"])
def test_before_import_is_left_out_where_the_os_gives_no_start(
        monkeypatch, fault):
    """Left out, not wrong: no ``/proc``, a ``stat`` that does not parse, or
    a start that would lie after the package's first line."""
    real_open = builtins.open

    def fake_open(path, *a, **kw):
        if path == "/proc/self/stat":
            if fault == "no_proc":
                raise FileNotFoundError(path)
            import io
            return io.BytesIO(b"1 (a b) c) S 0" if fault == "garbled"
                              else real_open(path, "rb").read())
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", fake_open)
    if fault != "after_the_import":
        assert startup.process_start_ns() is None
    private = FlightRecorder(capacity=8)
    t0_ns = (time.time_ns() if fault != "after_the_import"
             else startup.process_start_ns() - 10**9)
    startup.record_import(t0_ns, private)
    (imported,) = private.snapshot()
    assert imported["name"] == "startup.import" and imported["cause"] is None
    assert imported["t0_ns"] == t0_ns <= imported["t1_ns"]


def test_rows_without_a_finished_call_or_without_the_start():
    assert startup.time_to_first_step([]) is None
    ns = 10**9
    imported = {"name": "startup.import", "t0_ns": 5 * ns, "t1_ns": 7 * ns}
    call = {"name": "fit.call", "t0_ns": 8 * ns, "t1_ns": 12 * ns}
    assert startup.time_to_first_step([imported]) is None
    assert startup.time_to_first_step([call]) is None       # the start is gone
    rows = startup.time_to_first_step([imported, call])
    assert [(r["row"], r["s"]) for r in rows] == [
        ("total", 7.0), ("import", 2.0), ("init", 0), ("other", 5.0)]
    assert startup.format_rows(rows) == (
        "time to first step 7.0 s: import 2.0, init 0.0, other 5.0")
