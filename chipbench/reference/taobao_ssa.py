"""Plain float32 reference of the taobao_ssa ranker, and its seeded weights.

It follows the paper's Section V.A model as the configuration file states
it, and imports nothing of the program under test:

    history units: item row + category row + learned position   [L, d]
    pre-LN encoder blocks: LN -> Q,K,V (d x d) -> multi-head softmax
        attention over the valid keys -> O (d x d) -> residual;
        LN -> d x d_ff ReLU -> d_ff x d -> residual
    masked mean over the valid positions                         [d]
    tower([user, candidate, pooled, pooled * candidate]) -> hidden layers,
        PReLU after each -> 1; sigmoid of the logit.

The history does not depend on the candidate, so the reference encodes
each history once (`pooled`) and scores candidates against it (`scores`);
the program scores one row per candidate. Every matmul runs at `highest`
precision in the dtype of the weights it is given.

`make_weights` builds float32 weights in the tree layout the program
serves (`tables`, `pos`, `enc<l>`, `tower_*`). `quantize` is the
reference's own symmetric quantizer (Formulas 8-9 of the paper, per row
for tables, per output channel for 2-D linears), returned as float q * s.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def table_rows(cfg: dict) -> dict:
    """Table shapes as the program holds them: each vocabulary padded up
    to a multiple of `table_row_multiple`; padding rows are never read."""
    m = cfg["assumed"]["table_row_multiple"]

    def pad(v):
        return -(-v // m) * m

    return {"user": (pad(cfg["users"]), cfg["user_dim"]),
            "item": (pad(cfg["items"]), cfg["embed_dim"]),
            "category": (pad(cfg["categories"]), cfg["embed_dim"])}


def tower_in(cfg: dict) -> int:
    return cfg["user_dim"] + 3 * cfg["d_model"]


def weight_shapes(cfg: dict) -> dict:
    d, ff, L = cfg["d_model"], cfg["d_ff"], cfg["seq_len"]
    shapes = {"tables": table_rows(cfg), "pos": (L, d)}
    for l in range(cfg["n_blocks"]):
        shapes[f"enc{l}"] = {"ln1": (d,), "wq": (d, d), "wk": (d, d), "wv": (d, d),
                             "wo": (d, d), "ln2": (d,), "w1": (d, ff), "w2": (ff, d)}
    prev = tower_in(cfg)
    for i, h in enumerate(cfg["tower"]):
        shapes.update({f"tower_w{i}": (prev, h), f"tower_b{i}": (h,),
                       f"tower_a{i}": (h,)})
        prev = h
    shapes.update({"tower_wout": (prev, 1), "tower_bout": (1,)})
    return shapes


def _init(name: str, shape, key):
    """Random values for every leaf, biases and slopes included, so that a
    fault in any of them shows in the scores."""
    if name.startswith("ln"):
        return 1.0 + 0.1 * jax.random.normal(key, shape)
    if name.startswith("tower_b"):
        return 0.1 * jax.random.normal(key, shape)
    if name.startswith("tower_a"):
        return jax.random.uniform(key, shape, maxval=0.5)
    if name.startswith(("tower_w", "w")):
        return jax.random.normal(key, shape) / math.sqrt(shape[0])
    return 0.5 * jax.random.normal(key, shape)  # tables and positions


def make_weights(cfg: dict, key) -> dict:
    """Float32 weights from `key`; jit it to build them on the device."""
    shapes = weight_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = [_init(str(path[-1].key), shape, k).astype(jnp.float32)
              for (path, shape), k in zip(paths, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def quantize(w: dict, bits: int) -> dict:
    """Symmetric quantize-dequantize to `bits`: tables per row, 2-D linears
    per output channel; `pos` and 1-D leaves kept. Float q * s out."""
    top = 2.0 ** (bits - 1) - 1.0

    def qdq(x, axis):
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-12)
        return jnp.clip(jnp.round(x / s), -top, top) * s

    out = dict(w)
    out["tables"] = {k: qdq(v, 1) for k, v in w["tables"].items()}
    for k, v in w.items():
        if k.startswith("enc"):
            out[k] = {n: qdq(x, 0) if x.ndim == 2 else x for n, x in v.items()}
        elif k.startswith("tower_w"):
            out[k] = qdq(v, 0)
    return out


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=a.dtype)


def _ln(x, scale, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale


def _block(p, x, valid, n_heads, eps):
    R, L, d = x.shape
    dh = d // n_heads
    h = _ln(x, p["ln1"], eps)
    q, k, v = (_mm("rld,de->rle", h, p[n]).reshape(R, L, n_heads, dh)
               for n in ("wq", "wk", "wv"))
    s = _mm("rlhe,rmhe->rhlm", q, k) / jnp.sqrt(jnp.asarray(dh, x.dtype))
    s = jnp.where(valid[:, None, None, :], s, jnp.asarray(-1e30, x.dtype))
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("rhlm,rmhe->rlhe", a, v).reshape(R, L, d)
    x = x + _mm("rld,de->rle", o, p["wo"])
    h = _ln(x, p["ln2"], eps)
    f = jax.nn.relu(_mm("rld,df->rlf", h, p["w1"]))
    return x + _mm("rlf,fd->rld", f, p["w2"])


def pooled(w: dict, hist_item, hist_category, hist_len, cfg: dict):
    """Encoded histories [R, d] of R users: hist ids [R, L], lengths [R]."""
    t, eps = w["tables"], cfg["assumed"]["layer_norm_eps"]
    x = t["item"][hist_item] + t["category"][hist_category] + w["pos"][None]
    valid = jnp.arange(cfg["seq_len"])[None] < hist_len[:, None]
    for l in range(cfg["n_blocks"]):
        x = _block(w[f"enc{l}"], x, valid, cfg["n_heads"], eps)
    m = valid[..., None].astype(x.dtype)
    return jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)


def scores(w: dict, user, pooled_hist, cand_item, cand_category, cfg: dict):
    """Probabilities [R, C] of R requests: user ids [R], encoded histories
    [R, d], candidate ids [R, C]."""
    t = w["tables"]
    cand = t["item"][cand_item] + t["category"][cand_category]        # [R, C, d]
    p = jnp.broadcast_to(pooled_hist[:, None], cand.shape)
    u = t["user"][user]
    u = jnp.broadcast_to(u[:, None], cand.shape[:2] + u.shape[-1:])
    h = jnp.concatenate([u, cand, p, p * cand], axis=-1)
    for i in range(len(cfg["tower"])):
        h = _mm("rci,io->rco", h, w[f"tower_w{i}"]) + w[f"tower_b{i}"]
        h = jnp.where(h >= 0, h, w[f"tower_a{i}"] * h)
    out = _mm("rci,io->rco", h, w["tower_wout"]) + w["tower_bout"]
    return jax.nn.sigmoid(out[..., 0])


def request_flops(cfg: dict, n_cand: int) -> int:
    """Operations (2 per multiply-add) that one request needs: one history
    encode plus one tower row per candidate, whatever implements them."""
    L, d, ff = cfg["seq_len"], cfg["d_model"], cfg["d_ff"]
    block = 4 * 2 * L * d * d + 2 * 2 * L * L * d + 2 * 2 * L * d * ff
    dims = [tower_in(cfg), *cfg["tower"], 1]
    tower = 2 * sum(a * b for a, b in zip(dims, dims[1:]))
    return cfg["n_blocks"] * block + n_cand * tower
