"""The reduction from a profiler trace to the per-layer metrics."""
from __future__ import annotations

import pytest

import chipbench_testing  # noqa: F401  (puts the repo root on sys.path)
from chipbench import harness, tracemath


def _trace():
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.5), ("copy.3", 3.0, 4.0),
           ("fusion.1", 4.5, 6.0)]  # the last one runs past the window
    host = [("chipbench.window_open", 0.0, 0.001), ("chipbench.window_close", 4.999, 5.0),
            ("chipbench.dispatch", 1.4, 2.0), ("chipbench.assemble", 1.4, 1.8),
            ("chipbench.call", 1.8, 2.0), ("chipbench.wait", 2.0, 3.0),
            ("chipbench.fetch", 4.0, 4.4), ("chipbench.dispatch", 4.4, 4.5)]
    return tracemath.Trace(device_ops=[ops], host_spans=host)


def test_window_from_its_marks():
    assert tracemath.window(_trace()) == (0.0, 5.0)
    t = _trace()
    t.host_spans = t.host_spans[1:]
    with pytest.raises(RuntimeError):
        tracemath.window(t)


def test_busy_is_the_union_of_ops_inside_the_window():
    ops = _trace().device_ops[0]
    assert tracemath.union([("a", 0, 1), ("b", 0.5, 1.5), ("c", 2, 3)]) == [(0, 1.5), (2, 3)]
    assert tracemath.busy_s(ops, 0.0, 5.0) == pytest.approx(1.5 + 1.0 + 0.5)
    assert tracemath.idle_gaps(ops, 0.0, 5.0) == [(1.5, 3.0), (4.0, 4.5)]


def test_gaps_are_named_by_the_host_span_covering_most_of_them():
    t = _trace()
    assert tracemath.longest_gaps(t, 0.0, 5.0) == [["wait", 1.5], ["fetch", 0.5]]
    assert tracemath.host_at(t.host_spans, 10.0, 11.0) == "host_other"


def test_top_ops_sum_device_time_by_name_inside_the_window():
    top = tracemath.top_ops(_trace().device_ops[0], 0.0, 5.0, n=2)
    assert top == [["fusion.1", 1.5], ["fusion.2", 1.0]]


def test_dispatch_spans_that_start_in_the_window():
    assert tracemath.span_durations(_trace(), tracemath.DISPATCH_SPAN, 0.0, 5.0) == \
        pytest.approx([0.6, 0.1])


def _reading(**kw):
    r = dict(window_s=5.0, busy_s=2.5, rows=4050, flops=81 * 1e6, peak=1e12, chips=1,
             dispatch_s=[0.001, 0.003])
    return harness.Reading(**{**r, **kw})


@pytest.mark.parametrize("name, value", [
    ("device_idle.bulk", 50.0),
    ("device_us_per_row.rank", 1e6 * 2.5 / 4050),
    ("serve_step_mfu.bulk", 100.0 * 81 * 1e6 / (2.5 * 1e12)),
    ("host_ms_per_batch.rank", 2.0),
    ("host_ms_per_batch.bulk", 2.0),
])
def test_readers(name, value):
    assert harness.metric_reader(name)(_reading()) == pytest.approx(value)


@pytest.mark.parametrize("name", ["device_idle.bulk", "device_us_per_row.rank",
                                  "serve_step_mfu.bulk", "host_ms_per_batch.rank"])
def test_readers_with_nothing_to_read_return_none(name):
    r = _reading(busy_s=0.0, rows=0, flops=0.0, dispatch_s=[])
    assert harness.metric_reader(name)(r) is None


def test_load_reads_the_harness_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracemath.WINDOW_OPEN):
        pass
    for _ in range(3):
        with harness.span("dispatch"):
            y = f(jnp.ones(8))
        with harness.span("fetch"):
            y.block_until_ready()
    with jax.profiler.TraceAnnotation(tracemath.WINDOW_CLOSE):
        pass
    jax.profiler.stop_trace()
    t = tracemath.load(str(tmp_path))
    lo, hi = tracemath.window(t)
    assert hi > lo
    assert len(tracemath.span_durations(t, tracemath.DISPATCH_SPAN, lo, hi)) == 3
    assert t.device_ops == []  # the CPU has no TPU plane
