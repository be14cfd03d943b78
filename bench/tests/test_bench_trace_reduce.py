"""The trace reduction, on a trace recorded on one TPU v5e: three
`scatter_append` calls of 50 rows into a (65536, 3) buffer and a small
reduction after each, inside `bench.window` and `bench.answer_batch`
host spans."""
import os

import pytest

from bench import trace_reduce as TR
from bench.harness import Context, load_json, BENCH
from bench.metrics import device_idle_share, scatter_append_roofline

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "scatter_append_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return TR.reduce(TRACE)


def test_window_is_the_host_span(reduced):
    lo, hi = reduced.window
    assert hi - lo == pytest.approx(38_578_850, abs=1)
    assert reduced.window_s == pytest.approx(0.03857885)


def test_busy_is_the_union_of_ops_inside_the_window(reduced):
    # one device.  Its clock and the host's differ by about 0.1 ms: the
    # first call's ops are stamped before the host span that dispatched
    # them, so only the end of that call falls inside the window
    assert len(reduced.busy_ns) == 1
    assert reduced.busy_ns == [543373.0]
    idle = sum(b - a for a, b in reduced.idle_gaps) * 1e-9
    assert idle + reduced.busy_s == pytest.approx(reduced.window_s)


def test_program_time_by_stable_name(reduced):
    # programs are counted by their start, inside the window: the last
    # two of the three calls
    runs = reduced.modules["jit_scatter_append_pallas"]
    assert len(runs) == 2
    assert reduced.module_seconds("jit_scatter_append_pallas") == \
        pytest.approx((210197 + 210198) * 1e-9)


def test_breakdown_names_programs_and_host_activity(reduced):
    bd = TR.breakdown(reduced)
    assert bd["device_ops"][0][0] == "jit_scatter_append_pallas"
    labels = {name for name, _ in bd["idle_gaps"]}
    assert labels <= {"bench.window", "bench.answer_batch"}
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        reduced.window_s - reduced.busy_s)


def test_readers_on_the_recorded_trace(reduced):
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    ctx = Context(None, 0.0, 0, reduced, [(50, 3)] * 2, "TPU v5 lite", peaks)
    idle = device_idle_share.read(ctx)
    assert idle == pytest.approx((1 - 543373 / 38578850) * 100)
    share = scatter_append_roofline.read(ctx)
    want = 2 * 2 * 50 * 3 * 4 / 819e9 / (420395e-9) * 100
    assert share == pytest.approx(want)
    assert 0 < share < 1


def test_unknown_device_is_an_error(reduced):
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    ctx = Context(None, 0.0, 0, reduced, [(50, 3)], "TPU v9 imaginary", peaks)
    with pytest.raises(KeyError, match="no peaks"):
        scatter_append_roofline.read(ctx)


def test_readers_return_nothing_without_a_trace():
    ctx = Context(None, 0.0, 0, None, [(50, 3)], "TPU v5 lite", {})
    assert device_idle_share.read(ctx) is None
    assert scatter_append_roofline.read(ctx) is None
