"""The paper's Baseline (§V): sequential self-attention ranker on Taobao.

hist units (item⊕cat sum -> 64-d) + learned positions -> 2 pre-LN encoder
blocks (4-head self-attention + FFN 64->256->64) -> masked mean pool ->
tower([user16, cand64, pool64, pool*cand]) -> logit.

The serve path runs under three sibling top-level `jax.named_scope`s, so
that a profiler trace of the jitted step (`jit(serve_step)/<scope>/...` in
each op's `op_name`) splits device time by layer. The names are stable:

  embed    every field lookup, int8 gathers and their dequant included: the
           user and candidate item/category rows, the history item/category
           rows (one per history the batch holds) and the positional add
  encoder  the history mask, each block (`block{l}/attn`, `block{l}/ffn`)
           and the masked mean with its take to the rows (`pool`)
  tower    the concat, the MLP and the output sigmoid

The history encoding depends on `HISTORY_KEYS` alone, not on the candidate.
So a serve batch may hold each distinct history once: with `hist_row`
(int32[rows]) present, `hist_item`/`hist_category`/`hist_len` hold `cap`
histories, the encoder runs on those, and each candidate row takes the
pooled vector of history `hist_row[i]` (a gather under `encoder/pool`).
Without `hist_row` every row carries its own history.

Every projection is a compressible linear (core/lightweight.py), so the
full §III ladder — grouped/low-rank (C1), pruning masks (C4), int8 (C5) —
re-represents this model without touching this file. The teacher's
attention maps are exposed for the C3 KL distillation loss (Formula 3).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RecSysConfig
from repro.core.lightweight import linear
from repro.distributed.sharding import constrain
from repro.models.common import ParamDef
from repro.models.recsys.embedding import _take_rows, field_lookup, named_table_defs
from repro.models.recsys.rec_layers import bce_with_logits, mlp_apply, mlp_defs

# the batch keys the history encoding reads; it reads no candidate key
HISTORY_KEYS = ("hist_item", "hist_category", "hist_len")


def param_defs(cfg: RecSysConfig) -> Dict:
    d = cfg.d_attn  # 64
    L = cfg.seq_len
    defs: Dict = {"tables": named_table_defs(cfg)}
    defs["pos"] = ParamDef((L, d), (None, None), jnp.float32, "normal")
    for l in range(cfg.n_attn_layers):
        defs[f"enc{l}"] = {
            "ln1": ParamDef((d,), (None,), jnp.float32, "ones"),
            "wq": ParamDef((d, d), (None, None), jnp.float32, "fan_in"),
            "wk": ParamDef((d, d), (None, None), jnp.float32, "fan_in"),
            "wv": ParamDef((d, d), (None, None), jnp.float32, "fan_in"),
            "wo": ParamDef((d, d), (None, None), jnp.float32, "fan_in"),
            "ln2": ParamDef((d,), (None,), jnp.float32, "ones"),
            "w1": ParamDef((d, 4 * d), (None, None), jnp.float32, "fan_in"),
            "w2": ParamDef((4 * d, d), (None, None), jnp.float32, "fan_in"),
        }
    user_dim = cfg.field_dim([f for f in cfg.fields if f.name == "user"][0])
    tower_in = user_dim + d + d + d
    defs.update(mlp_defs("tower", tower_in, cfg.mlp_dims))
    return defs


def _ln(x, scale):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale


def _encoder_block(p, x, mask, n_heads: int, *, window: int = 0):
    """Pre-LN MHA + FFN. Returns (x, attention probs [B,H,L,L]) — the probs
    feed the C3 distillation KL. `window`>0 applies the paper's C2 local
    attention mask (|i-j| < window) at the model level."""
    B, L, d = x.shape
    dh = d // n_heads
    with jax.named_scope("attn"):
        h = _ln(x, p["ln1"])
        q = linear(p["wq"], h).reshape(B, L, n_heads, dh)
        k = linear(p["wk"], h).reshape(B, L, n_heads, dh)
        v = linear(p["wv"], h).reshape(B, L, n_heads, dh)
        s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(dh)
        valid = mask[:, None, None, :]  # key mask
        if window:
            ij = jnp.abs(jnp.arange(L)[:, None] - jnp.arange(L)[None, :]) < window
            valid = valid & ij[None, None]
        s = jnp.where(valid, s, -1e30)
        probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bhlm,bmhd->blhd", probs.astype(v.dtype), v).reshape(B, L, d)
        x = x + linear(p["wo"], o)
    with jax.named_scope("ffn"):
        h2 = _ln(x, p["ln2"])
        x = x + linear(p["w2"], jax.nn.relu(linear(p["w1"], h2)))
    return x, probs


def encode_history(params, batch, cfg: RecSysConfig, rules, collect_attn=False):
    """-> (pooled [B,d], attn list per layer). With `hist_row` in `batch`,
    pooled has one row per entry of `hist_row`, and the attention maps one
    per history."""
    t = params["tables"]
    with jax.named_scope("embed"):
        it = field_lookup(t, cfg, "hist_item", batch["hist_item"], rules)
        ca = field_lookup(t, cfg, "hist_category", batch["hist_category"], rules)
        x = it + ca + params["pos"][None]
    with jax.named_scope("encoder"):
        mask = jnp.arange(x.shape[1])[None] < batch["hist_len"][:, None]
        window = cfg_window(cfg)
        attns = []
        for l in range(cfg.n_attn_layers):
            with jax.named_scope(f"block{l}"):
                x, probs = _encoder_block(params[f"enc{l}"], x, mask, cfg.n_heads,
                                          window=window)
            if collect_attn:
                attns.append(probs)
        with jax.named_scope("pool"):
            m = mask[..., None].astype(x.dtype)
            pooled = jnp.sum(x * m, axis=1) / jnp.clip(jnp.sum(m, axis=1), 1.0)
            if "hist_row" in batch:
                pooled = pooled[batch["hist_row"]]
    return pooled, attns


def cfg_window(cfg) -> int:
    # C2 sparse attention window, carried via an optional attribute so the
    # base config dataclass stays family-generic.
    return getattr(cfg, "attn_window", 0) or 0


def _tower_logits(params, user, cand, pooled, cfg):
    x = jnp.concatenate([user, cand, pooled, pooled * cand], axis=-1)
    return mlp_apply(params, "tower", x, len(cfg.mlp_dims))[:, 0]


def logits_and_attn(params, batch, cfg: RecSysConfig, rules, collect_attn=False):
    t = params["tables"]
    with jax.named_scope("embed"):
        user = field_lookup(t, cfg, "user", batch["user"], rules)
        it = field_lookup(t, cfg, "item", batch["item"], rules)
        ca = field_lookup(t, cfg, "category", batch["category"], rules)
        cand = it + ca
    pooled, attns = encode_history(params, batch, cfg, rules, collect_attn)
    with jax.named_scope("tower"):
        out = constrain(_tower_logits(params, user, cand, pooled, cfg), ("batch",), rules)
    return out, attns


def logits(params, batch, cfg, rules):
    return logits_and_attn(params, batch, cfg, rules)[0]


def loss(params, batch, cfg: RecSysConfig, rules):
    lg = logits(params, batch, cfg, rules)
    b = bce_with_logits(lg, batch["label"])
    return b, {"bce": b}


def serve(params, batch, cfg: RecSysConfig, rules):
    lg = logits(params, batch, cfg, rules)
    with jax.named_scope("tower"):
        return jax.nn.sigmoid(lg)


def retrieval(params, query, cand_ids, cfg: RecSysConfig, rules):
    """History encoding is candidate-independent here — encode once, then
    batched tower over N candidates."""
    t = params["tables"]
    user = field_lookup(t, cfg, "user", query["user"], rules)[0]
    pooled, _ = encode_history(params, query, cfg, rules)
    pooled = pooled[0]

    it = _take_rows(t["item"], cand_ids)
    ca = _take_rows(t["category"], query["cand_category"])
    cand = it + ca
    cand = constrain(cand, ("candidates", None), rules)
    N = cand.shape[0]
    scores = _tower_logits(
        params,
        jnp.broadcast_to(user[None], (N, user.shape[0])),
        cand,
        jnp.broadcast_to(pooled[None], (N, pooled.shape[0])),
        cfg,
    )
    return constrain(scores, ("candidates",), rules)
