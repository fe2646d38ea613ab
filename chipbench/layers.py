"""The serve step split by the program's layers, in one traced window.

    python3 chipbench/layers.py --workload <cell> --seed <n> [--seconds <s>] [--trace 0|1]

Runs one cell as `run.py --trace 1` does: set-up, then the cell's window
with its first `harness.TRACE_SECONDS` traced. It prints one JSON line:

- `layer_us_per_row`: device time of each program layer per real row
  dispatched in the traced window. An op's layer is the first scope of its
  `op_name` path after the `jit(...)` prefix: `embed`, `encoder` or `tower`,
  and `unscoped` otherwise. `repro.models.recsys.taobao_ssa` names its
  layers with these scopes; any other model whose serve step names its
  layers with them is split the same way, with no edit here.
  The TPU's op events carry no `op_name`, so the path comes from the
  optimized HLO of each bucket's executable, by the op's name, result shape
  and opcode. A layer's time is the union of its ops' intervals, so layers
  whose ops overlap (async copies) may add to more than busy time.
  `top_ops` names each layer's three ops with most device time;
- `queue_ms_p50` (open loop): the median, over the requests dispatched in
  the traced window, of their batch's dispatch start minus their due time;
- `clock_offset_ms`: how far the device's clock in the trace runs ahead of
  the host's (negative: behind), from each serve-step execution paired with
  its launch and its completion on the host (`clock_offset`);
- `idle_gaps`: the longest idle stretches of the device, as [name, seconds,
  start after the window opened], each named by the harness span (or `gc`, a
  collection of Python's garbage collector) that covered most of it on the
  host's clock once the offset is taken off; `gc_ms`, each collection's
  length.

With `--trace 0` nothing is traced, and the line holds only
`rows_done_first_s`: rows completed in the first `harness.TRACE_SECONDS`,
to compare with a traced run's. Nothing here moves what `run.py` reports:
it reads the same kind of trace with functions of its own.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":  # the checkout's root and src/ in place of chipbench/
    sys.path[0:1] = [str(Path(__file__).resolve().parents[1]),
                     str(Path(__file__).resolve().parents[1] / "src")]

from chipbench import tracemath  # noqa: E402
from chipbench.tracemath import Interval  # noqa: E402

LAYERS = ("embed", "encoder", "tower")
UNSCOPED = "unscoped"
GC_SPAN = tracemath.SPAN_PREFIX + "gc"
LEAVES = tracemath.LEAF_SPANS + (GC_SPAN,)
CALL_SPAN = tracemath.SPAN_PREFIX + "call"
MODULES_LINE = "XLA Modules"
STEP_MODULE = "serve_step"  # the jitted function of `repro.launch.serve.make_serve_step`
# host events of the TPU runtime that carry the run_id of an execution
LAUNCH_EVENT, DONE_EVENT = "DoEnqueueProgram", "CompleteCallbacks"
PAIRS_PER_CHUNK = 64  # executions per estimate of the clock offset
_JIT = re.compile(r"^p?jit\(.*\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

Stamp = Tuple[float, Optional[int]]  # (time, run_id)


def layer_of(path: str) -> str:
    """The program layer of an op whose `op_name` is `path`."""
    parts = path.split("/")
    if not _JIT.match(parts[0]):
        return UNSCOPED  # an argument's layout copy, or an op XLA added
    while parts and _JIT.match(parts[0]):
        parts.pop(0)
    return parts[0] if parts and parts[0] in LAYERS else UNSCOPED


def layers_of_hlo(texts: List[str]) -> Dict[str, Optional[str]]:
    """`tracemath.op_name` of each instruction of the optimized HLO `texts`
    -> its layer; None where two programs give one name different layers."""
    out: Dict[str, Optional[str]] = {}
    for text in texts:
        for line in text.splitlines():
            line = line.strip().removeprefix("ROOT ")
            if not line.startswith("%"):
                continue
            m = _OP_NAME.search(line)
            key, layer = tracemath.op_name(line), layer_of(m.group(1) if m else "")
            out[key] = layer if out.get(key, layer) == layer else None
    return out


@dataclasses.dataclass
class LayerTrace:
    layer_ops: List[Dict[str, List[Interval]]]  # per chip: layer -> (op, start, end)
    modules: List[List[Tuple[float, float, Optional[int]]]]  # per chip: (start, end, run_id)
    host_spans: List[Interval]
    launches: List[Stamp]  # the runtime's enqueue of each execution
    completions: List[Stamp]  # the runtime's completion callbacks


def _run_id(e) -> Optional[int]:
    for k, v in e.stats:
        if k == "run_id":
            return int(v)
    return None


def load(trace_dir: str, hlo_layers: Dict[str, Optional[str]]) -> LayerTrace:
    """What the split reads of the one `.xplane.pb` under `trace_dir`; each
    device op takes its layer from `hlo_layers` (`layers_of_hlo`)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {files}")
    out = LayerTrace([], [], [], [], [])
    for plane in ProfileData.from_file(files[0]).planes:
        if tracemath.DEVICE_PLANE.match(plane.name):
            ops: Dict[str, List[Interval]] = defaultdict(list)
            mods = []
            for line in plane.lines:
                for e in line.events:
                    s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    if line.name == tracemath.OPS_LINE:
                        name = tracemath.op_name(e.name)
                        ops[hlo_layers.get(name) or UNSCOPED].append((name, s, t))
                    elif line.name == MODULES_LINE and STEP_MODULE in e.name:
                        mods.append((s, t, _run_id(e)))
            out.layer_ops.append(dict(ops))
            out.modules.append(sorted(mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    if e.name.startswith(tracemath.SPAN_PREFIX):
                        out.host_spans.append((e.name, s, s + e.duration_ns * 1e-9))
                    elif e.name in (LAUNCH_EVENT, DONE_EVENT):
                        (out.launches if e.name == LAUNCH_EVENT
                         else out.completions).append((s, _run_id(e)))
    return out


def layer_busy_s(layer_ops: List[Dict[str, List[Interval]]], lo: float,
                 hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi) in which some op of each layer ran, mean over the
    chips."""
    return {k: float(np.mean([tracemath.busy_s(ops.get(k, []), lo, hi) for ops in layer_ops]))
            for k in LAYERS + (UNSCOPED,)} if layer_ops else {}


def by_run_id(host: List[Stamp], modules: List[Stamp]) -> bool:
    return bool(host and modules) and all(r is not None for _, r in host + modules)


def pair(host: List[Stamp], modules: List[Stamp]) -> List[Tuple[float, float]]:
    """(host time, module time) of each host event and the execution it
    belongs to: by a shared run_id where every event on both sides carries
    one, else in order (one chip runs its launches first in, first out, and
    the trace opens with nothing in flight)."""
    if by_run_id(host, modules):
        at, first = {r: t for t, r in modules}, {}
        for t, r in sorted(host):  # a run's earliest event: a launch, or its first callback
            first.setdefault(r, t)
        return [(t, at[r]) for r, t in first.items() if r in at]
    return [(h[0], m[0]) for h, m in zip(sorted(host), sorted(modules))]


def clock_offset(launched: List[Tuple[float, float]], done: List[Tuple[float, float]]
                 ) -> Optional[Dict[str, float]]:
    """How far the device's clock runs ahead of the host's, in s.

    `launched` pairs each launch with its module's start, `done` each
    completion callback with its module's end. A module starts after its
    launch and ends before its callback, so the offset lies between the
    largest (end - callback) and the smallest (start - launch): the lower
    envelope, near zero once the offset is off where the device was idle at
    a launch. For each chunk of `PAIRS_PER_CHUNK` executions, the middle of
    that bracket is one estimate. Returns their median, min and max, and
    the median half-width of the brackets (`resolution`); None without
    launches. Without completions, the envelope alone is the estimate."""
    up = [m - h for h, m in sorted(launched, key=lambda p: p[1])]
    low = [m - h for h, m in sorted(done, key=lambda p: p[1])]
    if not up:
        return None
    est, half = [], []
    for i in range(0, len(up), PAIRS_PER_CHUNK):
        u = min(up[i:i + PAIRS_PER_CHUNK])
        d = low[i:i + PAIRS_PER_CHUNK]
        lo = max(d) if d else u
        est.append((u + lo) / 2)
        half.append((u - lo) / 2)
    return {"median": statistics.median(est), "min": min(est), "max": max(est),
            "resolution": statistics.median(half), "pairs": len(up)}


def host_at(spans: List[Interval], lo: float, hi: float) -> str:
    """The leaf span (a `gc` one included) that covers most of [lo, hi),
    where spans nest the inner one taking the time, without its prefix;
    "host_other" where no span of the harness covers it."""
    cover = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
             if n in LEAVES and e > lo and s < hi]
    cuts = sorted({lo, hi, *(s for _, s, _ in cover), *(e for _, _, e in cover)})
    total: Dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        inner = [(s, n) for n, s, e in cover if s <= a and e >= b]
        if inner:
            total[max(inner)[1]] += b - a
    best = max(total, key=total.get, default=None)
    return best[len(tracemath.SPAN_PREFIX):] if best else "host_other"


def longest_gaps(layer_ops: List[Dict[str, List[Interval]]], host_spans: List[Interval],
                 lo: float, hi: float, offset: float, n: int = 10) -> List[list]:
    """The `n` longest idle stretches of any chip, as [name, seconds, start
    after lo], each named on the host's clock: the device's less `offset`."""
    gaps = [g for ops in layer_ops
            for g in tracemath.idle_gaps([o for v in ops.values() for o in v], lo, hi)]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_at(host_spans, s - offset, e - offset), e - s, s - lo] for s, e in gaps[:n]]


def queue_s(starts: Dict[int, float], batches, due: np.ndarray, t_open: float,
            lo: float, hi: float) -> List[float]:
    """For each open-loop request whose batch's dispatch started in [lo, hi)
    (host clock), that start less the time it was due."""
    out: List[float] = []
    for b in batches:
        t = starts[b.first]
        if lo <= t < hi:
            out.extend(t - (t_open + due[b.first:b.stop]))
    return out


@contextlib.contextmanager
def gc_spans():
    """While open, each collection of Python's garbage collector is a host
    span `chipbench.gc` in the profiler's trace."""
    import jax

    open_spans = []

    def hook(phase, _info):
        if phase == "start":
            a = jax.profiler.TraceAnnotation(GC_SPAN)
            a.__enter__()
            open_spans.append(a)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)


class _Starts:
    """The model as `harness.serve` drives it, noting when each batch's
    dispatch started: `serve` assembles the batch first thing."""

    def __init__(self, model):
        self.model, self.step, self.starts = model, model.step, {}

    def batch(self, tr, first, stop, rows):
        self.starts[first] = time.perf_counter()
        return self.model.batch(tr, first, stop, rows)


def split(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One window of the cell, traced as `run.py --trace 1` traces it."""
    import jax

    from chipbench import harness

    harness.require_chips(cell["chips"])
    model, _, params, tr = harness.prepare(cfg, mix, seed, seconds)
    m = _Starts(model)
    win, on_time, trace_dir = {}, None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-layers-")

        def stop():
            win["t1"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(tracemath.WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()

        on_time = (min(seconds, harness.TRACE_SECONDS), stop)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(tracemath.WINDOW_OPEN):
            win["t0"] = time.perf_counter()
    with gc_spans() if trace else contextlib.nullcontext():
        batches, t_open, _ = harness.serve(m, params, tr, seconds, on_time)
    if trace and "t1" not in win:
        on_time[1]()
    first_s = t_open + min(seconds, harness.TRACE_SECONDS)
    out = {"rows_done_first_s": sum(b.rows for b in batches if b.t_done <= first_s)}
    if not trace:
        return out

    hlo = layers_of_hlo([
        model.step.lower(params, model.batch(tr, 0, tr.fill(0, tr.contents, b), b))
        .compile().as_text() for b in harness.buckets_used(tr)])
    t = load(trace_dir, hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = tracemath.window(tracemath.Trace(device_ops=[], host_spans=t.host_spans))
    rows = sum(b.rows for b in batches if win["t0"] <= b.t_dispatch < win["t1"])
    busy = layer_busy_s(t.layer_ops, lo, hi)
    modules = t.modules[0] if t.modules else []
    starts, ends = [(s, r) for s, _, r in modules], [(e, r) for _, e, r in modules]
    by_id = by_run_id(t.launches, starts)
    launched = t.launches if by_id else [(s, None) for n, s, _ in t.host_spans if n == CALL_SPAN]
    offset = clock_offset(pair(launched, starts),
                          pair(t.completions, ends) if by_run_id(t.completions, ends) else [])
    shift = offset["median"] if offset and abs(offset["median"]) > offset["resolution"] else 0.0
    out.update({
        "rows": rows, "window_s": hi - lo,
        "busy_s": float(np.mean([tracemath.busy_s([o for v in ops.values() for o in v], lo, hi)
                                 for ops in t.layer_ops])) if t.layer_ops else 0.0,
        "layer_s": busy,
        "layer_us_per_row": {k: 1e6 * v / rows for k, v in busy.items()} if rows else {},
        "top_ops": {k: tracemath.top_ops([o for ops in t.layer_ops for o in ops.get(k, [])],
                                         lo, hi, n=3) for k in LAYERS + (UNSCOPED,)},
        "ambiguous_op_names": sum(v is None for v in hlo.values()),
        "launches_paired_by": "run_id" if by_id else "order",
        "clock_offset_ms": {k: (v if k == "pairs" else 1e3 * v) for k, v in offset.items()}
        if offset else None,
        "gaps_shifted_ms": 1e3 * shift,
        "idle_gaps": longest_gaps(t.layer_ops, t.host_spans, lo, hi, shift),
        "idle_gaps_unshifted": longest_gaps(t.layer_ops, t.host_spans, lo, hi, 0.0),
        "gc_ms": [1e3 * (e - s) for n, s, e in t.host_spans if n == GC_SPAN and lo <= s < hi],
    })
    if tr.due is not None:
        q = queue_s(m.starts, batches, tr.due, t_open, win["t0"], win["t1"])
        out["queue_ms_p50"] = 1e3 * float(np.median(q)) if q else None
    return out


def main(argv=None) -> int:
    from chipbench import harness
    from chipbench.run import use_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; default the benchmark's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    use_cache()
    spec = harness.load_spec()
    cell, cfg, mix = harness.load_cell(spec, args.workload)
    try:
        out = split(cell, cfg, mix, args.seed, args.seconds or spec["run_seconds"],
                    bool(args.trace))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    off = out.get("clock_offset_ms")
    if off:
        below = " (below resolution: gaps named on the trace's clock)" \
            if out["gaps_shifted_ms"] == 0.0 else ""
        print(f"clock_offset_ms median {off['median']!r} range {off['min']!r} "
              f"{off['max']!r} resolution {off['resolution']!r}{below}",
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
