"""Drivers: one module `chipbench/models/<model>.py` for each model the
benchmark runs. A configuration file names its driver by its `"model"`
key, and `harness.model_for` imports it by that name, so a second model
joins the benchmark by new files alone: its driver, its plain reference,
its configuration files and entries in `BENCHMARK.json`.

A driver provides:

- `Model(cfg)`, built from the configuration file's dict, with
  - `step`: the program's jitted serve step, called as `step(params, batch)`;
    it returns the probability of each row of the batch, `[rows]`, as a
    device array (the harness calls `copy_to_host_async` and `is_ready` on
    it; `layers.py` lowers the step with `step.lower(params, batch)`);
  - `peak`: which of the device's peaks in `peaks.PEAKS` its work is counted
    against (`"bf16_flops"`, `"int8_ops"`, ...);
  - `params(key)`: the program's parameters made from the seed's key on the
    device, in one jitted call;
  - `batch(tr, first, stop, rows)`: requests [first, stop) of the traffic
    `tr` as the step's input, zero-padded to `rows` rows;
  - `request_flops(n_cand)`: the operations one request of `n_cand`
    candidates needs, counted from the configuration's shapes;
  - `reference(key, tr, pool, cand, control=False)`: the plain reference's
    probability of each row (pool user `pool`, candidate `cand`), from
    weights of its own made from the same key; with `control`, the same
    reference one precision step below the configuration's.
- `CPU_CUT`: the configuration keys and values that cut its vocabularies
  for the CPU tests; every width stays as published.
- `CPU_WIDTHS`: the small widths at which the CPU tests run a whole cell.
"""
