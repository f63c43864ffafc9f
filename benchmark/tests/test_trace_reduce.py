"""trace_reduce.py on a small RECORDED trace: the first 22 ms of the traced
part of `mistral7b_train_2k` on a TPU v5e (my chip run, PR 24), kept as an
XSpace text proto: real names, real times, instruction texts cut short."""
import os

import pytest

import tiny
import trace_reduce

DATA = os.path.join(tiny.HERE, "data", "train_trace_start.xspace.txt")


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return trace_reduce.load(text_proto=f.read())


def test_only_the_ops_line_and_the_benchmarks_spans_are_read(trace):
    assert list(trace.device_ops) == ["/device:TPU:0"]
    ops = trace.device_ops["/device:TPU:0"]
    assert len(ops) == 199                      # "XLA Modules" is left out
    assert all(" = " not in name and not name.startswith("%")
               for name, _, _ in ops)
    assert [n for n, _, _ in trace.host_spans] == [
        "bench.data_draw", "bench.train_step",
        "bench.data_draw", "bench.train_step"]  # PjitFunction is not ours


def test_busy_union_and_window(trace):
    # counted apart from the module: no two of these operations overlap, so
    # busy is the plain sum of their durations
    ops = trace.device_ops["/device:TPU:0"]
    assert trace_reduce.busy_seconds(trace) == pytest.approx(
        sum(d for _, _, d in ops) * 1e-9) == pytest.approx(0.017671828)
    assert trace_reduce.window_seconds(trace) == pytest.approx(0.021388982)
    assert trace_reduce.idle_share(trace) == pytest.approx(
        1 - 0.017671828 / 0.021388982)


def test_nested_operations_count_once():
    outer = ("while.1", 0.0, 100.0)
    inner = [("fusion.1", 10.0, 20.0), ("paged_attention.3", 40.0, 50.0)]
    t = trace_reduce.Trace({"/device:TPU:0": [outer] + inner}, [])
    assert trace_reduce.busy_seconds(t) == pytest.approx(100e-9)
    assert trace_reduce.kernel_seconds(t, "paged_attention") == \
        pytest.approx(50e-9)
    assert trace_reduce.top_device_ops(t) == [
        ["paged_attention", pytest.approx(50e-9)],
        ["fusion", pytest.approx(20e-9)]]       # the loop itself is no leaf


def test_kernel_sums(trace):
    assert trace_reduce.kernel_seconds(trace, "flash_attention_fwd") == \
        pytest.approx(0.001732209)
    assert trace_reduce.kernel_seconds(trace, r"^rms_norm_fwd") == \
        pytest.approx(0.000155334)
    assert trace_reduce.kernel_seconds(trace, "rope") == \
        pytest.approx(0.000100929)
    assert trace_reduce.kernel_seconds(trace, "fused_adamw") is None
    top = trace_reduce.top_device_ops(trace, 3)
    assert [n for n, _ in top] == ["fusion", "flash_attention_fwd", "copy"]
    assert top[0][1] == pytest.approx(0.014327075)


def test_gaps_are_named_by_the_host_span_open_in_them(trace):
    gaps = dict(trace_reduce.longest_idle_gaps(trace))
    # the device waits while the first train_step call is being dispatched
    assert gaps["bench.train_step"] == pytest.approx(0.003717029)
    assert sum(gaps.values()) == pytest.approx(0.021388982 - 0.017671828)


def test_means_over_chips():
    ops = [("fused_adamw.1", 0.0, 10.0)]
    t = trace_reduce.Trace({"/device:TPU:0": ops,
                            "/device:TPU:1": [("fused_adamw.2", 0.0, 30.0)]},
                           [])
    assert trace_reduce.busy_seconds(t) == pytest.approx(20e-9)
    assert trace_reduce.kernel_seconds(t, "fused_adamw") == \
        pytest.approx(20e-9)
