"""The serve step encodes each distinct history of a batch once.

A pointwise batch repeats each request's history on all of its candidate
rows. `ServeStep` sends each run of equal adjacent histories once, with
`hist_row`, and must answer every row as the plain per-row step does: for
requests of 1, 8 and 50 candidates (both capacities), for shuffled rows,
and for device arrays, which it passes through. At reduced widths, CPU.
"""
import functools

import jax
import numpy as np
import pytest

from test_serve_scopes import LAYERS, _equations

TOL = 1e-6
# what the benchmark counts as a compile inside its window
COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@pytest.fixture(scope="module")
def world():
    from repro.core.quantization import quantize_tree
    from repro.launch.serve import recsys_rules
    from repro.launch.train import make_data, sized_config
    from repro.models.common import init_params
    from repro.models.recsys import api as rec_api

    cfg = sized_config("taobao_ssa", "reduced")
    rules = recsys_rules()
    params = init_params(rec_api.param_defs(cfg), jax.random.key(0))
    pool = {k: np.asarray(v) for k, v in next(make_data(cfg, 256)(0)).items()}
    plain = jax.jit(lambda p, b: rec_api.serve(p, b, cfg, rules))
    return {"cfg": cfg, "rules": rules, "pool": pool, "plain": plain,
            "params": {"fp32": params, "int8": jax.jit(quantize_tree)(params)}}


def _batch(pool, requests: int, cands: int, rows: int, seed: int = 0) -> dict:
    """`requests` users of `pool`, each repeated on `cands` adjacent rows with
    candidates of their own, zero-padded to `rows`."""
    rng = np.random.default_rng(seed)
    user = np.repeat(rng.choice(len(pool["user"]), requests, replace=False), cands)
    out = {k: pool[k][user] for k in ("user", "hist_item", "hist_category", "hist_len")}
    out["item"] = rng.integers(0, pool["item"].max() + 1, len(user)).astype(np.int32)
    out["category"] = rng.integers(0, pool["category"].max() + 1, len(user)).astype(np.int32)
    return {k: np.pad(v, [(0, rows - len(user))] + [(0, 0)] * (v.ndim - 1))
            for k, v in out.items()}


def _step(world):
    from repro.launch.serve import make_serve_step

    return make_serve_step(world["cfg"], world["rules"])


# (requests, candidates each, rows, capacity sent)
SHAPES = {1: (60, 1, 64, 64), 8: (7, 8, 64, 8), 50: (5, 50, 256, 32)}


@pytest.mark.parametrize("cands", sorted(SHAPES))
@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_compact_step_answers_as_the_per_row_step(world, weights, cands):
    requests, _, rows, cap = SHAPES[cands]
    p, step = world["params"][weights], _step(world)
    b = _batch(world["pool"], requests, cands, rows)
    got = np.asarray(step(p, b))
    want = np.asarray(world["plain"](p, b))
    assert got.shape == (rows,)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert (step.rows, step.encoded) == (rows, cap)


@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_compact_step_answers_shuffled_rows(world, weights):
    p, step = world["params"][weights], _step(world)
    b = _batch(world["pool"], 7, 8, 64)
    order = np.random.default_rng(1).permutation(64)
    b = {k: v[order] for k, v in b.items()}
    np.testing.assert_allclose(np.asarray(step(p, b)), np.asarray(world["plain"](p, b)),
                               rtol=0, atol=TOL)


@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_device_arrays_pass_through(world, weights):
    p, step = world["params"][weights], _step(world)
    b = {k: jax.numpy.asarray(v) for k, v in _batch(world["pool"], 5, 50, 256).items()}
    assert step.compact(b) is None
    np.testing.assert_array_equal(np.asarray(step(p, b)), np.asarray(world["plain"](p, b)))
    assert (step.rows, step.histories, step.encoded) == (0, 0, 0)


def test_counters_read_rows_histories_and_capacity(world):
    from repro.launch.serve import history_runs

    p, step = world["params"]["fp32"], _step(world)
    b = _batch(world["pool"], 5, 50, 256)
    first, hist_row = history_runs(b, step.keys)
    np.testing.assert_array_equal(first, [0, 50, 100, 150, 200, 250])
    np.testing.assert_array_equal(hist_row, np.minimum(np.arange(256) // 50, 5))
    step(p, b)
    step(p, _batch(world["pool"], 60, 1, 64))
    # 5 requests and their padding, then 60 and theirs
    assert (step.rows, step.histories, step.encoded) == (256 + 64, 6 + 61, 32 + 64)


def test_the_other_capacity_compiles_nothing_later(world):
    p, step = world["params"]["fp32"], _step(world)
    step(p, _batch(world["pool"], 5, 50, 256))  # capacity 32, and 256 compiled beside it
    seen = []

    def on(name, *_, **__):
        if name in COMPILE_EVENTS:
            seen.append(name)

    jax.monitoring.register_event_listener(on)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        out = step(p, _batch(world["pool"], 250, 1, 256, seed=2))  # capacity 256
    finally:
        jax.monitoring.unregister_event_listener(on)
        jax.monitoring.unregister_event_duration_listener(on)
    assert step.encoded == 32 + 256
    assert out.shape == (256,) and seen == []


@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_every_compact_equation_lies_under_one_layer_scope(world, weights):
    p, step = world["params"][weights], _step(world)
    batch, layout = step.compact(_batch(world["pool"], 5, 50, 256))
    assert batch["hist_block"].shape == (32, 2 * 100 + 1) and batch["hist_row"][-1] == 5
    assert layout == (("hist_item", (100,)), ("hist_category", (100,)), ("hist_len", ()))
    shapes = jax.eval_shape(lambda: p)
    outer = jax.make_jaxpr(functools.partial(step.jitted, layout=layout))(shapes, batch).jaxpr
    (call,) = outer.eqns
    assert call.params["name"] == "serve_step"
    eqns = list(_equations(call.params["jaxpr"].jaxpr))
    layers = [name.split("/")[0] for name, _ in eqns]
    assert set(layers) == set(LAYERS), [e for e, l in zip(eqns, layers) if l not in LAYERS]
    assert not [e for e in eqns if e[1] in ("cond", "switch", "while") or "cond" in e[0]]
    gathers = [name for name, prim in eqns if prim == "gather"]
    pooled = [n for n in gathers if not n.startswith("embed")]
    # the tables are read under embed; the one other gather takes the pooled
    # vectors back to their rows
    assert len(pooled) == 1 and pooled[0].startswith("encoder/pool")
    assert len(gathers) > 1
