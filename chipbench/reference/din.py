"""Plain float32 reference of DIN, and its seeded weights.

It follows the public DIN code (github.com/mouna99/dien, `script/model.py`
`Model_DIN`, `script/utils.py` `din_attention`) at the widths the
configuration file states, and imports nothing of the program under test:

    behaviour unit k_t = item row ⊕ category row of history step t      [L, du]
    candidate      q   = item row ⊕ category row                         [du]
    attention      [q, k_t, q - k_t, q * k_t] -> sigmoid layers (`attn`)
                   -> linear to 1; softmax over the real steps (a padded
                   step scores -2**32 + 1); pooled = sum_t w_t k_t       [du]
    tower          [user, q, S, q * S, pooled], S = sum of k_t over the
                   real steps -> hidden layers, PReLU after each -> 1;
                   sigmoid of the logit.

Departures from the public code: PReLU, not Dice, in the tower (the paper
offers both, and so does the public `build_fcn_net`); no batch norm before
the tower (at inference an affine map, which the first linear absorbs); one
logit with a sigmoid, not a two-way softmax (the same function); S sums the
real steps only (the public code also sums the padded ones).

The units and S do not depend on the candidate, so the reference makes them
once per history (`history`) and scores rows against them (`scores`). Every
matmul runs at `highest` precision in the dtype of the weights it is given.

`make_weights` builds float32 weights in the tree layout the program
serves (`tables`, `attn_*`, `tower_*`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MASKED = -(2.0**32) + 1


def table_rows(cfg: dict) -> dict:
    """Table shapes as the program holds them: each vocabulary padded up
    to a multiple of `table_row_multiple`; padding rows are never read."""
    m, d = cfg["assumed"]["table_row_multiple"], cfg["embed_dim"]

    def pad(v):
        return -(-v // m) * m

    return {"user": (pad(cfg["users"]), d), "item": (pad(cfg["items"]), d),
            "category": (pad(cfg["categories"]), d)}


def unit_dim(cfg: dict) -> int:
    return 2 * cfg["embed_dim"]


def tower_in(cfg: dict) -> int:
    return cfg["embed_dim"] + 4 * unit_dim(cfg)


def _mlp_shapes(name: str, dims: list, slopes: bool) -> dict:
    shapes, prev = {}, dims[0]
    for i, h in enumerate(dims[1:]):
        shapes.update({f"{name}_w{i}": (prev, h), f"{name}_b{i}": (h,)})
        if slopes:
            shapes[f"{name}_a{i}"] = (h,)
        prev = h
    shapes.update({f"{name}_wout": (prev, 1), f"{name}_bout": (1,)})
    return shapes


def weight_shapes(cfg: dict) -> dict:
    return {"tables": table_rows(cfg),
            **_mlp_shapes("attn", [4 * unit_dim(cfg), *cfg["attn"]], slopes=False),
            **_mlp_shapes("tower", [tower_in(cfg), *cfg["tower"]], slopes=True)}


# Spread of the seeded weights. Each layer's weights are drawn at `scale` /
# sqrt(fan-in): the attention's wide enough that its scores differ across
# steps (sigmoids of unit-scale inputs would score every step alike), the
# tower's last narrow enough that the logits spread as a trained ranker's do
# (S sums up to 100 units, so the tower's input is wide).
SCALE = {"tables": 0.5, "attn_w0": 4.0, "attn_w1": 4.0, "attn_wout": 8.0,
         "tower_wout": 0.2}


def _init(name: str, shape, key):
    """Random values for every leaf, biases and slopes included, so that a
    fault in any of them shows in the scores."""
    kind = name.split("_", 1)[1][0] if "_" in name else "table"
    if kind == "b":
        return 0.1 * jax.random.normal(key, shape)
    if kind == "a":
        return jax.random.uniform(key, shape, maxval=0.5)
    if kind == "w":
        return SCALE.get(name, 1.0) * jax.random.normal(key, shape) / math.sqrt(shape[0])
    return SCALE["tables"] * jax.random.normal(key, shape)


def make_weights(cfg: dict, key) -> dict:
    """Float32 weights from `key`; jit it to build them on the device."""
    shapes = weight_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = [_init(str(path[-1].key), shape, k).astype(jnp.float32)
              for (path, shape), k in zip(paths, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _mm(a, b):
    return jnp.einsum("...i,io->...o", a, b, precision=HIGHEST, preferred_element_type=a.dtype)


def history(w: dict, hist_item, hist_category, hist_len):
    """(units [R, L, du], S [R, du], real steps [R, L]) of R histories:
    hist ids [R, L], lengths [R]."""
    t = w["tables"]
    units = jnp.concatenate([t["item"][hist_item], t["category"][hist_category]], axis=-1)
    valid = jnp.arange(hist_item.shape[1])[None] < hist_len[:, None]
    total = jnp.sum(jnp.where(valid[..., None], units, jnp.zeros((), units.dtype)), axis=1)
    return units, total, valid


def scores(w: dict, units, total, valid, user, cand_item, cand_category, cfg: dict):
    """Probabilities [R] of R rows: each row's history units [R, L, du],
    S [R, du] and real steps [R, L], and its user and candidate ids [R]."""
    t = w["tables"]
    q = jnp.concatenate([t["item"][cand_item], t["category"][cand_category]], axis=-1)
    qb = jnp.broadcast_to(q[:, None], units.shape)
    h = jnp.concatenate([qb, units, qb - units, qb * units], axis=-1)
    for i in range(len(cfg["attn"])):
        h = jax.nn.sigmoid(_mm(h, w[f"attn_w{i}"]) + w[f"attn_b{i}"])
    s = (_mm(h, w["attn_wout"]) + w["attn_bout"])[..., 0]
    a = jax.nn.softmax(jnp.where(valid, s, jnp.asarray(MASKED, s.dtype)), axis=-1)
    pooled = jnp.einsum("rl,rld->rd", a, units, precision=HIGHEST,
                        preferred_element_type=units.dtype)
    x = jnp.concatenate([t["user"][user], q, total, q * total, pooled], axis=-1)
    for i in range(len(cfg["tower"])):
        x = _mm(x, w[f"tower_w{i}"]) + w[f"tower_b{i}"]
        x = jnp.where(x >= 0, x, w[f"tower_a{i}"] * x)
    return jax.nn.sigmoid((_mm(x, w["tower_wout"]) + w["tower_bout"])[:, 0])


def request_flops(cfg: dict, n_cand: int) -> int:
    """Operations (2 per multiply-add) that one request needs: for each
    candidate, the attention MLP and the weighted sum at all `seq_len`
    history steps (the program, like the public code over its padded
    batch, runs them at every step), then one tower row."""
    L, du = cfg["seq_len"], unit_dim(cfg)
    att = [4 * du, *cfg["attn"], 1]
    tower = [tower_in(cfg), *cfg["tower"], 1]
    per_step = sum(a * b for a, b in zip(att, att[1:])) + du
    return n_cand * 2 * (L * per_step + sum(a * b for a, b in zip(tower, tower[1:])))
