"""The arithmetic from intervals to busy, idle, gaps and self time, on
hand-made lists."""

import pytest

from benchmarks.suite import xplane


def test_merge_and_union_length():
    iv = [(5, 7), (0, 2), (1, 3), (3, 3), (6, 6.5)]
    assert xplane.merge(iv) == [(0, 3), (5, 7)]
    assert xplane.union_length(iv) == 5


@pytest.mark.parametrize("intervals,window,want", [
    ([(1, 2), (4, 6)], (0, 10), [(0, 1), (2, 4), (6, 10)]),
    ([(0, 10)], (0, 10), []),
    ([], (2, 3), [(2, 3)]),
    ([(-5, 1), (9, 20)], (0, 10), [(1, 9)]),
    ([(1, 4), (2, 3)], (0, 5), [(0, 1), (4, 5)]),
])
def test_gaps(intervals, window, want):
    assert xplane.gaps(intervals, window) == want


def test_idle_counts_overlapping_ops_once():
    from benchmarks.suite.readers import device_idle
    import types

    # two ops overlap on [2, 3]: busy is 5 of 10 (4 + 1), not 6
    trace = xplane.Trace(devices={0: [("a", 0, 3), ("b", 2, 4),
                                      ("c", 9, 10)]}, spans=[])
    assert trace.window() == (0, 10) and trace.busy_seconds() == 5
    res = types.SimpleNamespace(trace=trace)
    assert device_idle.read(None, res) == pytest.approx(50.0)


def test_self_times_do_not_count_children_twice():
    events = [("while", 0, 10), ("fusion.1", 1, 4), ("copy.2", 4, 6),
              ("inner", 4.5, 5.5), ("fusion.3", 12, 13)]
    got = dict(xplane.self_times(events))
    assert got == {"while": 5, "fusion.1": 3, "copy.2": 1, "inner": 1,
                   "fusion.3": 1}
    assert sum(got.values()) == xplane.union_length(
        [(s, e) for _, s, e in events])


def test_trace_reductions():
    trace = xplane.Trace(
        devices={0: [("fusion.1", 0, 4), ("all-gather.2", 4, 5),
                     ("fusion.7", 6, 10)],
                 1: [("fusion.1", 0, 2), ("all-gather.2", 2, 5),
                     ("fusion.7", 6, 10)]},
        spans=[("sched.step", 0, 10), ("prefill", 5, 5.8)])
    assert trace.window() == (0, 10)
    assert trace.busy_seconds() == 9
    assert trace.op_seconds("all-gather") == 2
    assert trace.op_seconds("all-gather", device=1) == 3
    assert trace.top_ops(1) == [["fusion", 8]]
    assert trace.top_gaps() == [["prefill", 1]]
    assert xplane.innermost_span(trace.spans, 20) == "no_span"


def test_collective_time_in_flight_and_exposed():
    import types

    from benchmarks.suite.readers import op_time

    coll = r"^(all-gather|collective-permute)"
    trace = xplane.Trace(
        devices={0: [("all-gather-start.1 all-gather-start", 0.0, 0.001),
                     ("fusion.1 fusion", 0.001, 0.030),
                     ("all-gather-done.1 all-gather-done", 0.030, 0.040),
                     ("collective-permute.2 collective-permute", .05, .06)]},
        asyncs={0: [("all-gather-start.1 all-gather-start", 0.0, 0.040)]},
        spans=[])
    res = types.SimpleNamespace(trace=trace, facts={"profiled_steps": 2})
    in_flight = op_time.read(None, res, pattern=coll, per="step",
                             line="flight")
    exposed = op_time.read(None, res, pattern=coll, per="step")
    assert in_flight == pytest.approx(25.0)         # 40 + 10 ms, 2 steps
    assert exposed == pytest.approx(10.5)           # 1 + 10 + 10 ms
    assert xplane.short_name(
        '%attn.29 = f32[768,1,64]{2,1,0:T(1,128)S(1)} custom-call(s32[48]'
        '{0:T(128)S(1)} %copy-done.216), custom_call_target="tpu_custom_'
        'call", operand_layout_constraints={}') == \
        "attn.29 custom-call:tpu_custom_call"
    assert xplane.short_name("%copy.456.remat = f32[385,16,128,64]{3,2,1,0"
                             ":T(8,128)} copy(%p)") == "copy.456.remat copy"
    assert xplane.category("copy.456.remat copy") == "copy copy"
    assert xplane.short_name("bench:decode") == "bench:decode"


def test_load_returns_none_without_a_device_plane(tmp_path):
    assert xplane.load(str(tmp_path)) is None
