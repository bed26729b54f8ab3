"""trace_reduce.py on a small recorded trace: busy union, idle share, the
products' time, dispatch gaps, and one idle gap blamed on a host span."""
import os

import pytest
from jax.profiler import ProfileData

import trace_reduce
from conftest import HERE


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        return trace_reduce.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_union_and_gaps():
    merged = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace_reduce.gaps(merged, 0, 10) == [(3, 5), (8, 10)]


def test_busy_union_and_idle_share(reduced):
    # four dispatches of 6 ms, 4 ms idle after each; the first is left out
    # (tracing may have begun inside it) and the window is the two whole
    # periods from the second start to the last. The while spans its body's
    # operations and must not be counted twice
    assert reduced["window_s"] == pytest.approx(20e-3)
    assert reduced["busy_s"] == pytest.approx(12e-3)
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.4)


def test_products_and_ops(reduced):
    # the output fusion (2 ms) and the bare convolution (1 ms), two periods
    assert reduced["matmul_s"] == pytest.approx(6e-3)
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["maximum_add_fusion.2 kLoop bf16[128,56,56,256]"] == pytest.approx(6e-3)
    assert not any(k.startswith("while") for k in ops)


def test_dispatches(reduced):
    assert reduced["step_module"] == "jit_wrapped(123)"
    assert reduced["dispatches"] == 2
    assert reduced["dispatch_gap_ms_p50"] == pytest.approx(10.0)


def test_gap_blamed_on_the_innermost_host_span(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert [s for _, s in gaps] == pytest.approx([4e-3, 4e-3])
    assert sorted(n for n, _ in gaps) == ["python3: <unknown> astype",
                                          "python3: shape_base.py:371 stack"]


def test_limit_cuts_the_slice():
    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        r = trace_reduce.reduce_profile(
            ProfileData.from_text_proto(f.read()), limit_s=8e-3)
    # cut to the one dispatch that ended inside the slice: it stands alone
    assert r["window_s"] == pytest.approx(6e-3)
    assert r["busy_s"] == pytest.approx(6e-3)
    assert r["dispatches"] == 1


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'))
