"""The trace reduction on a small synthetic trace whose answers are known
by hand."""
import pytest

from bench import trace

MS = 1_000_000  # ns


def synthetic() -> trace.Trace:
    # window 0..100 ms; device 0 busy 10-30 and 25-40 (overlap) and 70-80,
    # device 1 busy 0-50; host spans: plan 40-70 covers device 0's long
    # gap, traverse 5-45 and 65-85
    return trace.Trace(
        window=(0, 100 * MS),
        devices={
            0: [("fusion.1", 10 * MS, 20 * MS),
                ("fold_count_max", 25 * MS, 15 * MS),
                ("fusion.1", 70 * MS, 10 * MS)],
            1: [("all-to-all.3", 0, 50 * MS)],
        },
        spans=[("traverse", 5 * MS, 40 * MS), ("plan", 40 * MS, 30 * MS),
               ("traverse", 65 * MS, 20 * MS)])


def test_busy_is_the_union_averaged_over_devices():
    # device 0: 10-40 and 70-80 = 40 ms; device 1: 50 ms
    assert trace.busy_s(synthetic()) == pytest.approx(0.045)


def test_idle_share():
    assert trace.idle_share(synthetic()) == pytest.approx(0.55)


def test_idle_share_without_a_device_is_none():
    assert trace.idle_share(trace.Trace(window=(0, MS))) is None


def test_busy_clips_to_the_window():
    tr = synthetic()
    tr.window = (20 * MS, 60 * MS)
    # device 0: 20-40 = 20 ms; device 1: 20-50 = 30 ms
    assert trace.busy_s(tr) == pytest.approx(0.025)


def test_op_seconds_and_events_match_by_name():
    tr = synthetic()
    assert trace.op_seconds(tr, r"^fusion") == pytest.approx(0.030)
    assert trace.op_seconds(tr, "all-to-all|collective-permute") == \
        pytest.approx(0.050)
    assert [e[0] for e in trace.op_events(tr, "fold_count_max")] == \
        ["fold_count_max"]


def test_top_ops_orders_by_device_time():
    top = trace.top_ops(synthetic())
    assert [n for n, _ in top] == ["all-to-all.3", "fusion.1",
                                   "fold_count_max"]
    assert top[0][1] == pytest.approx(0.050)


def test_top_ops_count_a_loop_by_its_own_time():
    # a loop 0-100 holding two body ops of 30 and 20 ms, one of which
    # holds a nested op of 5 ms
    ops = [("%while.1 = (s32[]) while(...)", 0, 100 * MS),
           ("%fusion.2 = f32[8]{0} fusion(...)", 10 * MS, 30 * MS),
           ("%fusion.3 = f32[8]{0} fusion(...)", 15 * MS, 5 * MS),
           ("%fusion.4 = f32[8]{0} fusion(...)", 50 * MS, 20 * MS)]
    tr = trace.Trace(window=(0, 100 * MS), devices={0: ops})
    got = dict(trace.top_ops(tr))
    assert got == {"while.1 s32[]": pytest.approx(0.050),
                   "fusion.2 f32[8]": pytest.approx(0.025),
                   "fusion.4 f32[8]": pytest.approx(0.020),
                   "fusion.3 f32[8]": pytest.approx(0.005)}
    assert trace.busy_s(tr) == pytest.approx(0.100)


def test_idle_gaps_are_labelled_by_the_covering_span():
    # device 0 idles 0-10 (traverse 5 ms, no span 5 ms), 40-70 (plan)
    # and 80-100 (traverse 5 ms)
    gaps = trace.idle_gaps(synthetic())
    assert gaps[0] == ["plan", pytest.approx(0.030)]
    assert [g[0] for g in gaps[1:]] == ["traverse", "traverse"]
    assert [g[1] for g in gaps[1:]] == [pytest.approx(0.020),
                                        pytest.approx(0.010)]
