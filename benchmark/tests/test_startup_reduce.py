"""The readers of ``setup_s``'s parts (``startup_reduce.py`` and the nine
metric files over it) on a canned ring: each number worked out by hand,
``None`` on an empty ring and on one that dropped records."""
import time

import pytest

import run
import startup_reduce
from deeplearning4j_tpu.observability import flight_recorder as fr_mod
from deeplearning4j_tpu.observability.flight_recorder import FlightRecorder

NEW = ("setup.before_import_s", "setup.import_s", "setup.init_s",
       "setup.first_stage_s", "compile.resolve_s", "compile.deserialize_s",
       "setup.first_steps_s", "setup.program_s",
       "device.step_program_temp_gb")

#: the canned start, in seconds from the process's: name, t0, t1, fields.
#: The window starts at 40
CANNED = [
    ("startup.before_import", 0.0, 12.5, {}),
    ("startup.import", 12.5, 14.5, {}),
    ("startup.init", 15.0, 15.25, {}),
    ("startup.init", 16.0, 16.5, {}),
    ("input.h2d", 20.05, 20.125, {"group": 0}),
    ("input.h2d", 20.3, 20.4, {"group": 1}),
    ("compile.store_read", 20.5, 21.0, {"group": 0}),
    ("compile.deserialize", 21.0, 24.0, {"group": 0}),
    ("compile.resolve", 20.5, 24.25,
     {"group": 0, "kind": "compile", "fn": "Net.multistep", "hit": True,
      "temp_bytes": 3_500_000_000}),
    ("fit.dispatch", 20.5, 24.5, {"group": 0, "path": "Net.multistep"}),
    ("fit.listeners", 24.5, 28.5, {"group": 0}),
    ("fit.dispatch", 28.5, 28.75, {"group": 1, "path": "Net.multistep"}),
    ("fit.listeners", 28.75, 30.5, {"group": 1}),
    ("fit.call", 20.0, 31.0, {}),
    # an init that overlaps the call by a second, and a program resolved
    # outside any call
    ("startup.init", 30.0, 33.0, {}),
    ("compile.deserialize", 32.0, 32.25, {}),
    ("compile.resolve", 32.0, 32.5,
     {"kind": "compile", "fn": "Net.output", "hit": True, "temp_bytes": 7}),
    # the window's: none of it is set-up's
    ("input.h2d", 40.5, 40.6, {"group": 2}),
    ("compile.deserialize", 45.0, 45.5, {"group": 2}),
    ("compile.resolve", 45.0, 46.0,
     {"group": 2, "kind": "compile", "fn": "Net.other", "hit": True}),
    ("fit.dispatch", 41.0, 47.0, {"group": 2, "path": "Net.multistep"}),
    ("fit.listeners", 47.0, 47.5, {"group": 2}),
    ("fit.call", 40.0, 71.0, {}),
]

BY_HAND = {
    "setup.before_import_s": 12.5,
    "setup.import_s": 2.0,
    "setup.init_s": 0.25 + 0.5 + 3.0,
    "setup.first_stage_s": 20.125 - 20.0,
    "compile.resolve_s": 3.75 + 0.5,
    "compile.deserialize_s": 3.0 + 0.25,
    "setup.first_steps_s": 28.5 - 24.25,
    # import 2 + inits 0.25 + 0.5 + the call and the init across its end,
    # 20 to 33, once
    "setup.program_s": 2.0 + 0.25 + 0.5 + 13.0,
    "device.step_program_temp_gb": 3.5,
}


@pytest.fixture
def readers():
    found = {d["name"]: read
             for d, read in run.load_metrics("resnet50-train-b128")}
    assert set(NEW) <= set(found)       # every cell loads all nine
    return {name: found[name] for name in NEW}


@pytest.fixture
def canned(monkeypatch):
    """``fill(records)`` puts ``records`` into the program's ring, the
    process having started 100 s ago; returns the readers' ``ctx``."""
    monkeypatch.setattr(startup_reduce, "_said", set())
    zero_ns = time.time_ns() - 100 * 10**9
    zero_clock = time.perf_counter() - 100.0

    def fill(records, capacity=64):
        rec = FlightRecorder(capacity=capacity)
        monkeypatch.setattr(fr_mod, "_GLOBAL", rec)
        for name, t0, t1, fields in records:
            fields = dict(fields)
            rec.record_span(name, zero_ns + round(t0 * 1e9),
                            zero_ns + round(t1 * 1e9),
                            kind=fields.pop("kind", "span"), **fields)
        return {"window": {"t_start": zero_clock + 40.0, "dispatches": 1},
                "setup_s": 39.9}

    return fill


@pytest.mark.parametrize("name", NEW)
def test_each_reader_gives_the_number_worked_out_by_hand(
        readers, canned, name):
    ctx = canned(CANNED)
    assert readers[name](ctx) == pytest.approx(BY_HAND[name], abs=1e-4)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_reads_nothing_from_an_empty_ring(readers, canned, name):
    assert readers[name](canned([])) is None


@pytest.mark.parametrize("name", NEW)
def test_each_reader_reads_nothing_from_a_ring_that_dropped(
        readers, canned, name, capsys):
    ctx = canned(CANNED, capacity=len(CANNED) - 1)
    assert fr_mod.global_recorder().dropped == 1
    assert readers[name](ctx) is None
    assert "the ring dropped 1 records" in capsys.readouterr().err


@pytest.mark.parametrize("name", NEW)
def test_an_older_programs_ring_reads_nothing_and_raises_nothing(
        readers, canned, name):
    """The parent's program: the groups' spans, a bare ``compile`` event,
    none of this PR's."""
    old = [r for r in CANNED if r[0] in ("input.h2d", "fit.dispatch",
                                         "fit.listeners")]
    ctx = canned(old)
    fr_mod.global_recorder().record("compile", fn="Net.multistep",
                                    wall_s=3.0, cache_hit=True)
    assert readers[name](ctx) is None


def test_program_s_counts_overlapping_spans_once_and_says_the_rest(
        readers, canned, capsys):
    ctx = canned(CANNED)
    assert readers["setup.program_s"](ctx) == pytest.approx(15.75, abs=1e-4)
    # setup_s 39.9 - before the import 12.5 - the program 15.75
    err = capsys.readouterr().err
    assert "the harness between the program's phases 11.650" in err
    span = lambda t0, t1: {"t0_ns": t0, "t1_ns": t1}
    assert startup_reduce.union_seconds([]) == 0
    assert startup_reduce.union_seconds(
        [span(0, 4 * 10**9), span(10**9, 2 * 10**9),       # inside
         span(3 * 10**9, 6 * 10**9),                       # across the end
         span(8 * 10**9, 9 * 10**9)]) == 7.0               # apart


def test_a_run_that_compiled_reads_zero_deserialize(readers, canned):
    cold = [r for r in CANNED if r[0] != "compile.deserialize"]
    ctx = canned(cold)
    assert readers["compile.deserialize_s"](ctx) == 0
    assert readers["compile.resolve_s"](ctx) == pytest.approx(4.25, abs=1e-4)


def test_first_steps_start_at_the_dispatch_where_it_resolved_nothing(
        readers, canned):
    warmed = [r for r in CANNED if not (r[0].startswith("compile.")
                                        and r[3].get("group") == 0)]
    assert readers["setup.first_steps_s"](canned(warmed)) == pytest.approx(
        28.5 - 20.5, abs=1e-4)


def test_temp_gb_is_absent_where_the_runtime_gave_no_figure(readers, canned):
    bare = [(n, t0, t1, {k: v for k, v in f.items() if k != "temp_bytes"})
            for n, t0, t1, f in CANNED]
    assert readers["device.step_program_temp_gb"](canned(bare)) is None
