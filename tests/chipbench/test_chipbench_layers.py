"""The split of a traced window by the program's layers (`chipbench/layers.py`)."""
from __future__ import annotations

import gc

import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (puts the repo root on sys.path)
from chipbench import layers, tracemath


@pytest.mark.parametrize("path, layer", [
    ("jit(serve_step)/embed/jit(_take)/gather", "embed"),
    ("jit(serve_step)/encoder/block0/attn/bhlm,bmhd->blhd/dot_general", "encoder"),
    ("jit(serve_step)/tower/logistic", "tower"),
    ("jit(serve_step)/jit(_where)/tower/select_n", "tower"),
    ("jit(serve_step)/mul", "unscoped"),
    ("p['tables']['item']", "unscoped"),  # the layout copy of an argument
    ("embed/gather", "unscoped"),  # no jit(...) prefix: not a path of the program
    ("", "unscoped"),
])
def test_an_op_takes_the_first_scope_after_the_jit_prefix(path, layer):
    assert layers.layer_of(path) == layer


def test_an_op_takes_its_layer_from_the_programs_hlo_by_name_shape_and_opcode():
    hlo = '  %fusion.3 = f32[8,4]{1,0} fusion(%a), metadata={op_name="jit(serve_step)/embed/g"}'
    copy = "  %copy.1 = f32[9,4]{1,0} copy(%p), metadata={op_name=\"p['tables']['item']\"}"
    root = "  ROOT " + hlo.strip().replace("fusion.3", "fusion.4").replace("embed", "tower")
    assert layers.layers_of_hlo([hlo + "\n" + copy, root]) == {
        "fusion.3 f32[8,4] fusion": "embed", "copy.1 f32[9,4] copy": "unscoped",
        "fusion.4 f32[8,4] fusion": "tower"}
    # another bucket's program gives one name another layer: neither is trusted
    other = hlo.replace("embed", "encoder")
    assert layers.layers_of_hlo([hlo, other]) == {"fusion.3 f32[8,4] fusion": None}
    assert layers.layers_of_hlo([hlo, hlo.replace("/g", "/h")]) == \
        {"fusion.3 f32[8,4] fusion": "embed"}


def test_a_layers_time_is_the_union_of_its_ops_in_the_window_mean_over_chips():
    chip0 = {"embed": [("g.1", 0.0, 1.0), ("g.2", 0.5, 1.5)],  # overlap counts once
             "encoder": [("f.1", 1.0, 3.0)],
             "unscoped": [("copy.1", 0.2, 0.4), ("copy.1", 4.5, 6.0)]}  # past the window
    chip1 = {"embed": [("g.1", 0.0, 0.5)]}
    busy = layers.layer_busy_s([chip0, chip1], 0.0, 5.0)
    assert busy == pytest.approx({"embed": (1.5 + 0.5) / 2, "encoder": 1.0, "tower": 0.0,
                                  "unscoped": (0.2 + 0.5) / 2})
    # async copies overlap compute, so the layers may add to more than busy
    assert sum(layers.layer_busy_s([chip0], 0, 5).values()) > \
        tracemath.busy_s([o for v in chip0.values() for o in v], 0, 5)
    assert layers.layer_busy_s([], 0.0, 5.0) == {}


def _planted(offset=-2e-3, chunks=1):
    """Executions 10 ms apart on a device clock `offset` ahead of the host's
    (negative: behind). Each starts 20-60 us after its launch and ends 1 ms
    later, 30-50 us before its completion callback; the host fetches from
    0.046 to 0.0499 s and waits from 0.0501 to 0.058 s."""
    n = layers.PAIRS_PER_CHUNK * chunks
    rng = np.random.default_rng(0)
    launch = 0.01 * np.arange(n)
    start = launch + 20e-6 + 40e-6 * rng.random(n)
    done = start + 1e-3 + 30e-6 + 20e-6 * rng.random(n)
    runs = list(range(100, 100 + n))
    modules = [(s + offset, r) for s, r in zip(start, runs)]
    ends = [(s + 1e-3 + offset, r) for s, r in zip(start, runs)]
    spans = [("chipbench.call", t - 1e-4, t + 1e-5) for t in launch] + \
        [("chipbench.fetch", 0.046, 0.0499), ("chipbench.wait", 0.0501, 0.058)]
    return list(zip(launch, runs)), modules, list(zip(done, runs)), ends, spans


@pytest.mark.parametrize("by", ["run_id", "order"])
def test_pairing_recovers_a_planted_clock_offset(by):
    launches, modules, done, ends, _ = _planted(chunks=2)
    if by == "order":
        launches = [(t, None) for t, _ in launches][::-1]  # order, not position in the list
    pairs = layers.pair(launches, modules)
    assert sorted(pairs) == [(t, m) for (t, _), (m, _) in zip(sorted(launches), modules)]
    off = layers.clock_offset(pairs, layers.pair(done, ends))
    assert off["pairs"] == 2 * layers.PAIRS_PER_CHUNK
    # the bracket: start - launch >= offset >= end - callback, tight to tens of us
    assert -2e-3 - 50e-6 <= off["min"] <= off["median"] <= off["max"] <= -2e-3 + 60e-6
    assert 0 < off["resolution"] < 50e-6
    # without completions the launch envelope alone: above the offset, near it
    alone = layers.clock_offset(pairs, [])
    assert -2e-3 + 20e-6 <= alone["median"] <= -2e-3 + 60e-6 and alone["resolution"] == 0
    assert layers.clock_offset([], []) is None


def test_a_run_pairs_by_its_earliest_host_event():
    modules = [(1.0, 7), (2.0, 8)]
    host = [(0.9, 7), (0.95, 7), (1.9, 8), (5.0, 9)]  # 9 is another program's
    assert sorted(layers.pair(host, modules)) == [(0.9, 1.0), (1.9, 2.0)]
    assert not layers.by_run_id([(0.9, None)], modules)


def test_a_gap_is_named_on_the_host_clock_once_the_offset_is_off():
    launches, modules, done, ends, spans = _planted()
    off = layers.clock_offset(layers.pair(launches, modules), layers.pair(done, ends))
    # the device idles from 0.0501 to 0.0516 on the host's clock: 0.0481-0.0496 on its own
    ops = [{"encoder": [("f", 0.0, 0.0481), ("f", 0.0496, 0.1)]}]
    raw = layers.longest_gaps(ops, spans, 0.0, 0.1, 0.0, n=1)
    fixed = layers.longest_gaps(ops, spans, 0.0, 0.1, off["median"], n=1)
    assert raw[0][0] == "fetch" and fixed[0][0] == "wait"
    assert fixed[0][1] == pytest.approx(0.0015) and fixed[0][2] == pytest.approx(0.0481)


def test_a_collection_inside_a_fetch_names_its_gap_gc():
    spans = [("chipbench.fetch", 0.0, 0.1), ("chipbench.gc", 0.002, 0.097),
             ("chipbench.dispatch", 0.1, 0.2)]
    assert tracemath.host_at(spans, 0.0, 0.1) == "fetch"  # gc is no leaf there
    assert layers.host_at(spans, 0.0, 0.1) == "gc"
    assert layers.host_at(spans, 0.097, 0.1) == "fetch"
    assert layers.host_at(spans, 0.3, 0.4) == "host_other"


def test_queue_wait_is_dispatch_start_less_due_time():
    class B:
        def __init__(self, first, stop):
            self.first, self.stop = first, stop

    due = np.array([0.0, 0.001, 0.002, 0.010])
    starts = {0: 100.0025, 3: 100.0105, 4: 200.0}
    q = layers.queue_s(starts, [B(0, 3), B(3, 4), B(4, 5)], due, 100.0, 100.0, 101.0)
    assert q == pytest.approx([0.0025, 0.0015, 0.0005, 0.0005])


def test_collections_are_spans_of_a_recorded_trace(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracemath.WINDOW_OPEN):
        pass
    with layers.gc_spans():
        gc.collect()
    gc.collect()  # the hook is gone
    with jax.profiler.TraceAnnotation(tracemath.WINDOW_CLOSE):
        pass
    jax.profiler.stop_trace()
    t = layers.load(str(tmp_path), {})
    assert [n for n, _, _ in t.host_spans].count(layers.GC_SPAN) == 1
    assert t.layer_ops == [] and t.modules == []  # the CPU has no TPU plane


@pytest.mark.parametrize("cell_name", ["taobao_fp32-rank50", "taobao_int8-bulk"])
def test_a_whole_split_at_small_widths(cell_name):
    """`layers.split` runs a cell as `run.py` does; the CPU has no device
    plane, so what it reads of the device is empty."""
    import jax

    from chipbench import harness

    cell, cfg, m = chipbench_testing.small_cell(cell_name)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        traced = layers.split(cell, cfg, m, 2**31 + 7, 0.5, True)
        plain = layers.split(cell, cfg, m, 2**31 + 7, 0.5, False)
    assert traced["rows"] > 0 and plain["rows_done_first_s"] > 0
    assert traced["layer_us_per_row"] == {} and traced["clock_offset_ms"] is None
    assert traced["top_ops"] == {k: [] for k in layers.LAYERS + (layers.UNSCOPED,)}
    if m["arrivals"] == "poisson":
        assert traced["queue_ms_p50"] >= 0.0
    else:
        assert "queue_ms_p50" not in traced
