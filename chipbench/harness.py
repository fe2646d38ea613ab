"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

The dispatcher is the benchmark's own and has no knobs. Requests are
served first come, first served. While fewer than `IN_FLIGHT` batches are
on the device, it takes every queued whole request that fits `MAX_ROWS`
rows (81 requests of 50 candidates), pads them to the smallest bucket of
`BUCKETS` and calls the program's serve step. A request is done when its
batch's scores are on the host; its latency is timed from when it was due.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from chipbench import peaks, tracemath
from chipbench.traffic import Traffic, make_traffic

ROOT = Path(__file__).resolve().parents[1]
BUCKETS = tuple(64 * 2**i for i in range(7))  # 64 ... 4096 rows
MAX_ROWS = BUCKETS[-1]
IN_FLIGHT = 2
POLL_S = 50e-6
TRACE_SECONDS = 4.0  # of a traced run's window, what the profiler records
COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def bucket_for(rows: int) -> int:
    for b in BUCKETS:
        if rows <= b:
            return b
    raise ValueError(f"{rows} rows exceed the largest bucket {MAX_ROWS}")


def buckets_used(tr: Traffic) -> List[int]:
    """The buckets the requests of `tr` can fill. A saturated batch is full:
    the next request would not fit, so it holds more than `MAX_ROWS` less
    the largest request."""
    most = int(tr.count.max())
    if not 1 <= int(tr.count.min()) <= most <= MAX_ROWS:
        raise ValueError(f"requests of {tr.count.min()}-{most} candidates fit no bucket")
    least = MAX_ROWS - most + 1 if tr.due is None else int(tr.count.min())
    return [b for b in BUCKETS if bucket_for(least) <= b]


@dataclasses.dataclass
class Batch:
    first: int
    stop: int
    rows: int
    t_dispatch: float
    out: object = None
    t_done: float = 0.0
    scores: Optional[np.ndarray] = None  # [rows]


def span(name: str):
    """A host span in the profiler's trace (next to free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(tracemath.SPAN_PREFIX + name)


def serve(model, params, tr: Traffic, seconds: float,
          on_time: Optional[tuple] = None) -> tuple:
    """Serve `tr` for `seconds`. Returns (batches, t_open, t_close).

    Open loop (`tr.due` set): every request due before `seconds` is served,
    also where that takes past the close. Saturated: full batches until
    the close, then the ones in flight finish. `on_time` = (t, fn) calls
    fn() once the clock passes t seconds after the open."""
    clock = time.perf_counter
    inflight: deque = deque()
    batches: List[Batch] = []

    def dispatch(first: int, stop: int) -> None:
        rows = tr.rows(first, stop)
        with span("dispatch"):
            with span("assemble"):
                batch = model.batch(tr, first, stop, bucket_for(rows))
            with span("call"):
                out = model.step(params, batch)
                out.copy_to_host_async()
        inflight.append(Batch(first, stop, rows, clock(), out))

    def retire() -> None:
        b = inflight.popleft()
        with span("fetch"):
            s = np.asarray(b.out)
        b.t_done, b.out = clock(), None
        b.scores = s[:b.rows]
        batches.append(b)

    t_open = clock()
    t_close = t_open + seconds
    hook_at = t_open + on_time[0] if on_time else None
    nxt = 0
    if tr.due is None:
        while clock() < t_close:
            if hook_at is not None and clock() >= hook_at:
                on_time[1]()
                hook_at = None
            if len(inflight) < IN_FLIGHT:
                stop = tr.fill(nxt, nxt + MAX_ROWS, MAX_ROWS)
                dispatch(nxt, stop)
                nxt = stop
            else:
                retire()
    else:
        due = t_open + tr.due
        total = len(due)
        while nxt < total or inflight:
            now = clock()
            if hook_at is not None and now >= hook_at:
                on_time[1]()
                hook_at = None
            queued = int(np.searchsorted(due, now, side="right"))
            if len(inflight) < IN_FLIGHT and queued > nxt:
                stop = tr.fill(nxt, queued, MAX_ROWS)
                dispatch(nxt, stop)
                nxt = stop
            elif inflight and (len(inflight) == IN_FLIGHT or inflight[0].out.is_ready()):
                retire()
            else:
                with span("wait"):
                    while True:
                        now = clock()
                        if nxt < total and due[nxt] <= now:
                            break
                        if inflight and inflight[0].out.is_ready():
                            break
                        until = due[nxt] - now if nxt < total else POLL_S
                        time.sleep(min(until, POLL_S) if inflight else until)
    while inflight:
        retire()
    return batches, t_open, t_close


class CompileCounter:
    """Counts compile requests and traces while it is open."""

    def __enter__(self):
        import jax

        self.count = 0
        self._on = True
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _event(self, name, **_):
        if self._on and name in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        self._event(name)

    def __exit__(self, *exc):
        self._on = False  # jax.monitoring has no way to remove one listener


def latencies_ms(batches: List[Batch], tr: Traffic, t_open: float) -> np.ndarray:
    """Latency of every open-loop request, from when it was due."""
    out = np.empty(len(tr.due))
    for b in batches:
        out[b.first:b.stop] = 1e3 * (b.t_done - t_open - tr.due[b.first:b.stop])
    return out


def answers(model, key, tr: Traffic, batches: List[Batch], control: bool = False) -> tuple:
    """(served, reference) probabilities of every served row, in the order
    served; with `control`, the control's in place of the reference's."""
    if not batches:
        return np.zeros(0), np.zeros(0)
    served = np.concatenate([b.scores for b in batches]).astype(np.float64)
    pool, cand = (np.concatenate(a) for a in zip(*(tr.row_index(b.first, b.stop)
                                                   for b in batches)))
    return served, model.reference(key, tr, pool, cand, control).astype(np.float64)


OFF_BY = 2e-3  # an answer this far from the reference's probability is off


def readings(served: np.ndarray, reference: np.ndarray) -> dict:
    """What `correct` compares, of every served answer against the
    reference: the share of answers off by more than `OFF_BY`. A non-finite
    answer is off; where nothing was served, every answer is."""
    gap = np.where(np.isfinite(served), np.abs(served - reference), np.inf)
    return {"share_off_2e-3": float(np.mean(~(gap <= OFF_BY))) if gap.size else 1.0}


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(spec: dict, name: str) -> tuple:
    """(cell, configuration, mix) of the workload `name`."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, mix


def driver(cfg: dict):
    """The driver module `chipbench/models/<cfg["model"]>.py`, to the
    contract in `chipbench/models/__init__.py`."""
    return importlib.import_module(f"chipbench.models.{cfg['model']}")


def model_for(cfg: dict):
    """`Model(cfg)` of the configuration's driver."""
    return driver(cfg).Model(cfg)


def metric_reader(name: str) -> Callable:
    """`read` of chipbench/metrics/<name>.py."""
    path = ROOT / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell_name: str, kind: str) -> List[dict]:
    """The `kind` ("end_to_end" or "per_layer") metrics the cell reports."""
    e2e = [m for m in spec["end_to_end"] if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell_name in m.get("workloads", [cell_name] if m["moves"] in moved else [])]


@dataclasses.dataclass
class Reading:
    """What the per-layer readers read, over the traced window."""
    window_s: float
    busy_s: float                  # mean over the chips used
    rows: int                      # real rows dispatched in the window
    flops: float                   # the work those requests need
    peak: float                    # the configuration's peak, per second per chip
    chips: int
    dispatch_s: List[float]        # host time of each dispatch span


def e2e_value(name: str, setup_s: float, batches: List[Batch], tr: Traffic,
              t_open: float, t_close: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "p50_ms":
        return float(np.percentile(latencies_ms(batches, tr, t_open), 50))
    if name == "rows_per_s":
        return sum(b.rows for b in batches if b.t_done <= t_close) / (t_close - t_open)
    raise KeyError(f"no end-to-end metric {name!r}")


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"JAX found {len(devices)} {devices[0].platform} device(s); "
                     f"this cell needs {chips} TPU chip(s)")
    return devices[:chips]


def prepare(cfg: dict, mix: dict, seed: int, seconds: float) -> tuple:
    """Set-up: the model, its weights from `seed` on the device, every
    request of the run, and each bucket the mix fills run once."""
    import jax

    model = model_for(cfg)
    key = jax.random.key(seed)
    params = jax.block_until_ready(model.params(key))
    tr = make_traffic(mix, cfg, seed, seconds)
    for b in buckets_used(tr):
        np.asarray(model.step(params, model.batch(tr, 0, tr.fill(0, tr.contents, b), b)))
    return model, key, params, tr


def check(model, key, tr: Traffic, batches: List[Batch], limits: dict) -> tuple:
    """(correct, attempted, failed, checks): every served answer against
    the reference, once the program's state is freed."""
    answered = sum(b.stop - b.first for b in batches)
    attempted = len(tr.due) if tr.due is not None else answered
    served, reference = answers(model, key, tr, batches)
    got = readings(served, reference)
    checks = {"unanswered": {"value": attempted - answered, "limit": 0},
              **{n: {"value": got[n], "limit": lim} for n, lim in limits.items()}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    rows = np.concatenate([tr.count[tr.content(b.first, b.stop)] for b in batches] or [[0]])
    bad = np.add.reduceat(~np.isfinite(served), np.cumsum(rows) - rows) if served.size else []
    return correct, attempted, attempted - answered + int(np.count_nonzero(bad)), checks


def run_cell(cell: dict, cfg: dict, mix: dict, metrics: List[dict], seed: int,
             seconds: float, trace: bool, t_start: float) -> dict:
    """One run of one cell; returns the result object. `metrics` are the
    cell's end-to-end metrics, or with `trace` its per-layer ones;
    `t_start` is the clock reading at which the process began."""
    import jax

    devices = require_chips(cell["chips"])
    model, key, params, tr = prepare(cfg, mix, seed, seconds)
    setup_s = time.perf_counter() - t_start

    win, on_time = {}, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")

        def stop():
            win["t1"] = time.perf_counter()
            with jax.profiler.TraceAnnotation(tracemath.WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()

        on_time = (min(seconds, TRACE_SECONDS), stop)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(tracemath.WINDOW_OPEN):
            win["t0"] = time.perf_counter()
    with CompileCounter() as compiles:
        batches, t_open, t_close = serve(model, params, tr, seconds, on_time)
    if trace and "t1" not in win:
        on_time[1]()
    print(f"compiles_in_window {compiles.count}", flush=True)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    values, extra = {}, {}
    if trace:
        t = tracemath.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tracemath.window(t)
        in_win = [b for b in batches if win["t0"] <= b.t_dispatch < win["t1"]]
        busy = float(np.mean([tracemath.busy_s(ops, lo, hi) for ops in t.device_ops])) \
            if t.device_ops else 0.0
        r = Reading(window_s=hi - lo, busy_s=busy, rows=sum(b.rows for b in in_win),
                    flops=sum(model.request_flops(int(n)) for b in in_win
                              for n in tr.count[tr.content(b.first, b.stop)]),
                    peak=peaks.peak(devices[0].device_kind, model.peak),
                    chips=len(devices),
                    dispatch_s=tracemath.span_durations(t, tracemath.DISPATCH_SPAN, lo, hi))
        for m in metrics:
            v = metric_reader(m["name"])(r)
            if v is not None:
                values[m["name"]] = v
        extra = {"busy_s": busy, "window_s": hi - lo}
        breakdown = {"device_ops": tracemath.top_ops(
                         [o for ops in t.device_ops for o in ops], lo, hi),
                     "idle_gaps": tracemath.longest_gaps(t, lo, hi)}
    else:
        values = {m["name"]: e2e_value(m["name"], setup_s, batches, tr, t_open, t_close)
                  for m in metrics}

    del params
    gc.collect()
    t_check = time.perf_counter()
    correct, attempted, failed, checks = check(model, key, tr, batches, cfg["limits"])
    print(f"reference_s {time.perf_counter() - t_check!r}", file=sys.stderr, flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    units = {m["name"]: m["unit"] for m in metrics}
    result = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
              "metrics": {n: {"value": v, "unit": units[n]} for n, v in values.items()},
              "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                         "count": len(devices), "memory_peak_bytes": int(memory_peak),
                         **extra}}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
