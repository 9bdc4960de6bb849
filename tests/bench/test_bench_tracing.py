"""The trace reduction on small synthetic traces (bench/tracing.py)."""
import pytest

from bench import tracing


def test_union_merges_overlaps_and_nesting():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (22, 25, "d")]
    assert tracing.union(ops) == [(0, 15), (20, 30)]
    assert tracing.covered(ops) == 25


def test_idle_share_is_one_minus_union_over_window():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c")]
    # window [0, 40]: busy 25 of 40
    assert tracing.idle_share(ops, 0, 40) == pytest.approx(100 * 15 / 40)
    # ops are clipped to the window
    assert tracing.busy_ns(ops, 8, 22) == pytest.approx(9)


def test_exposed_collective_time():
    ops = [(0, 10, "fusion.1"),
           (5, 20, "all-gather.3"),      # 10..20 exposed
           (30, 40, "all-reduce-start"),  # 30..35 hidden, 35..40 exposed
           (30, 35, "convolution.2"),
           (50, 60, "reduce-scatter.1"),  # all hidden
           (45, 70, "fusion.9")]
    assert tracing.exposed_collective_ns(ops, 0, 100) == pytest.approx(15)
    assert tracing.is_collective("all-to-all.7")
    assert not tracing.is_collective("fusion.all-gather")


def test_idle_gaps_are_named_by_the_host_span_covering_them():
    ops = [(0, 10, "a"), (30, 40, "b")]
    spans = [(0, 100, "bench.window"), (9, 28, "bench.batch_fetch"),
             (28, 31, "bench.step_dispatch")]
    gaps = tracing.idle_gaps(ops, spans, 0, 50)
    assert gaps[0] == ["bench.batch_fetch", pytest.approx(20e-9)]
    assert gaps[1] == ["untraced host work", pytest.approx(10e-9)]


def test_top_ops_sum_by_name():
    ops = [(0, 10, "x"), (10, 30, "y"), (30, 35, "x")]
    assert tracing.top_ops(ops, 0, 100) == [["y", pytest.approx(20e-9)],
                                            ["x", pytest.approx(15e-9)]]


def test_window_comes_from_the_window_span():
    t = tracing.Trace(devices={}, spans=[(3, 9, "bench.window")])
    assert t.window() == (3, 9)
    with pytest.raises(ValueError):
        tracing.Trace(devices={}, spans=[]).window()


def test_op_name_from_tpu_event_text():
    text = ("%all-gather.3 = bf16[4,3072]{1,0:T(8,128)(2,1)} "
            "all-gather(bf16[1,3072]{1,0} %p), dimensions={0}")
    assert tracing.op_name(text) == "all-gather.3"
    assert tracing.is_collective(tracing.op_name(text))
    assert tracing.op_name("fusion.1") == "fusion.1"
