"""DIN on the system's serve path: `ServeStep` gathers each distinct history
once, the rows take it back by `hist_row`, and the answers are the per-row
step's; every equation of the step lies under one layer scope; retrieval
scores as the serve path does. At the reduced vocabularies of
`sized_config`, published widths, CPU. And the compacted step at the
benchmark's cut (`chipbench/configs/din-fp32.json`), compiled for a
described v5e chip."""
import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_serve_compact import SHAPES, TOL, _batch
from test_serve_scopes import LAYERS, _equations
from test_tpu_compile import ROOT, _fits, _on, one_chip, topo  # noqa: F401 (fixtures)


@pytest.fixture(scope="module")
def world():
    from repro.launch.serve import recsys_rules
    from repro.launch.train import make_data, sized_config
    from repro.models.common import init_params
    from repro.models.recsys import api as rec_api

    cfg = sized_config("din", "reduced")
    rules = recsys_rules()
    defs = rec_api.param_defs(cfg)
    keys = iter(jax.random.split(jax.random.key(0), 64))
    # every bias and PReLU slope random, so that each one shows in the answers
    params = jax.tree.map(lambda a: 0.1 * jax.random.normal(next(keys), a.shape) if a.ndim == 1
                          else a, init_params(defs, jax.random.key(1)))
    pool = {k: np.asarray(v) for k, v in next(make_data(cfg, 256)(0)).items()}
    plain = jax.jit(lambda p, b: rec_api.serve(p, b, cfg, rules))
    return {"cfg": cfg, "rules": rules, "params": params, "pool": pool, "plain": plain}


def _step(world):
    from repro.launch.serve import make_serve_step

    return make_serve_step(world["cfg"], world["rules"])


def test_din_declares_its_history_keys(world):
    from repro.models.recsys import api

    assert api.history_keys(world["cfg"]) == ("hist_item", "hist_category", "hist_len")


@pytest.mark.parametrize("cands", sorted(SHAPES))
def test_compact_step_answers_as_the_per_row_step(world, cands):
    requests, _, rows, cap = SHAPES[cands]
    step = _step(world)
    b = _batch(world["pool"], requests, cands, rows)
    got = np.asarray(step(world["params"], b))
    want = np.asarray(world["plain"](world["params"], b))
    assert got.shape == (rows,) and np.all((got > 0) & (got < 1))
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert (step.rows, step.histories, step.encoded) == (rows, requests + (rows > requests * cands),
                                                         cap)


def _scoped_equations(world, compact: bool):
    step = _step(world)
    b = _batch(world["pool"], 5, 50, 256)
    shapes = jax.eval_shape(lambda: world["params"])
    if compact:
        b, layout = step.compact(b)
        fn = functools.partial(step.jitted, layout=layout)
    else:
        fn = step.jitted
    (call,) = jax.make_jaxpr(fn)(shapes, b).jaxpr.eqns  # the jitted step itself
    assert call.params["name"] == "serve_step"
    return list(_equations(call.params["jaxpr"].jaxpr))


@pytest.mark.parametrize("compact", [False, True], ids=["pointwise", "compact"])
def test_every_serve_equation_lies_under_one_layer_scope(world, compact):
    eqns = _scoped_equations(world, compact)
    layers = [name.split("/")[0] for name, _ in eqns]
    assert set(layers) == set(LAYERS), [e for e, l in zip(eqns, layers) if l not in LAYERS]
    # every gather, the take of each history to its rows included, is embed
    gathers = [name for name, prim in eqns if prim == "gather"]
    assert len(gathers) >= 5 + compact and all(n.startswith("embed") for n in gathers)
    assert {"/".join(n.split("/")[1:3]) for n, _ in eqns if n.startswith("encoder/")} == \
        {"target_attn/mlp", "target_attn/pool"}
    # the attention MLP runs per row: its matmuls are all under encoder
    dots = [name for name, prim in eqns if prim == "dot_general"]
    assert sum(n.startswith("encoder/target_attn/mlp") for n in dots) == 3
    assert sum(n.startswith("tower") for n in dots) == 3


def test_retrieval_scores_as_the_serve_path(world):
    from repro.models.recsys import api

    cfg, rules, p = world["cfg"], world["rules"], world["params"]
    b = _batch(world["pool"], 1, 64, 64)
    query = {k: b[k][:1] for k in ("user", "hist_item", "hist_category", "hist_len")}
    query["cand_category"] = b["category"]
    got = jax.jit(lambda p, q, c: api.retrieval(p, q, c, cfg, rules))(p, query, b["item"])
    want = np.asarray(world["plain"](p, b))
    np.testing.assert_allclose(np.asarray(jax.nn.sigmoid(got)), want, rtol=0, atol=TOL)


def test_compact_step_compiles_for_the_chip_at_the_published_cut(one_chip):
    """`din-fp32`'s compacted 4,096-row step on one described v5e: it fits
    the chip with the tables as its arguments, gathers each history's items
    once (512 x 100 rows, never 4,096 x 100), and copies neither the item
    nor the user table."""
    from chipbench.models.din import program_config
    from repro.launch.serve import ROWS_PER_HISTORY, make_serve_step, recsys_rules
    from repro.models.common import init_params
    from repro.models.recsys import api

    cfg = program_config(json.loads((ROOT / "chipbench" / "configs" / "din-fp32.json").read_text()))
    params = jax.eval_shape(lambda: init_params(api.param_defs(cfg), jax.random.key(0)))
    rows, L, d = 4096, cfg.seq_len, cfg.embed_dim
    cap = rows // ROWS_PER_HISTORY
    per_row = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    batch = {"user": per_row, "item": per_row, "category": per_row, "hist_row": per_row,
             "hist_block": jax.ShapeDtypeStruct((cap, 2 * L + 1), jnp.int32, sharding=one_chip)}
    layout = (("hist_item", (L,)), ("hist_category", (L,)), ("hist_len", ()))
    step = make_serve_step(cfg, recsys_rules())
    compiled = step.jitted.lower(_on(params, one_chip), batch, layout=layout).compile()
    _fits(compiled)
    assert compiled.memory_analysis().argument_size_in_bytes >= 5_947_200_000
    text = compiled.as_text()
    assert f"f32[{cap * L},{d}]" in text and f"f32[{rows * L},{d}]" not in text
    copied = {shape for line in text.splitlines() if re.search(r"\bcopy(-start)?\(", line)
              for shape in re.findall(r"f32\[[\d,]+\]", line.split(" copy")[0])}
    tables = params["tables"]
    assert copied and not {f"f32[{tables[t].shape[0]},{d}]" for t in ("item", "user")} & copied
