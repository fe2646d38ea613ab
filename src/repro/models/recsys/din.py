"""DIN [arXiv:1706.06978]: target attention over the behaviour sequence.

The equations are those of the public DIN code (github.com/mouna99/dien,
`script/model.py` `Model_DIN`, `script/utils.py` `din_attention`):

  behaviour unit  k_t = item_emb ⊕ category_emb of step t                [L, du]
  candidate       q = item_emb ⊕ category_emb                            [du]
  attention       per step: [q, k_t, q − k_t, q · k_t] -> 80 -> 40, sigmoid
                  after each -> linear to 1; softmax over the real steps;
                  pooled = Σ_t w_t k_t                                    [du]
  tower           [user, q, Σ k, q · Σ k, pooled] -> 200 -> 80, PReLU after
                  each -> 1 logit; sigmoid for the probability.

Departures from the public code, each the same function or a sum it states
more plainly: PReLU and not Dice (the paper offers both, and so does the
public `build_fcn_net`); no batch norm before the tower (at inference an
affine map, which the first linear absorbs); one logit with a sigmoid, not a
two-way softmax (the same function); Σ k over the real steps only (the
public code also sums the padded ones).

The serve path runs under the three sibling top-level `jax.named_scope`s
that `taobao_ssa` uses, so that a profiler trace splits device time by
layer (`jit(serve_step)/<scope>/...` in each op's `op_name`):

  embed    every table gather (user, candidate item and category, history
           item and category), Σ k over the real steps, and the take of
           each history to its rows by `hist_row`
  encoder  the target attention: `target_attn/mlp` (the features and the
           attention MLP) and `target_attn/pool` (masked softmax, weighted
           sum)
  tower    the concat, the MLP and the output sigmoid

The behaviour units and Σ k depend on `HISTORY_KEYS` alone, not on the
candidate. So a serve batch may hold each distinct history once: with
`hist_row` (int32[rows]) present, `hist_item`/`hist_category`/`hist_len`
hold `cap` histories, each gathered once, and every row takes the units of
history `hist_row[i]`. Without `hist_row` every row carries its own history.
The attention and the tower depend on the candidate and run per row.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import RecSysConfig
from repro.distributed.sharding import constrain
from repro.models.recsys.embedding import _take_rows, field_lookup, named_table_defs
from repro.models.recsys.rec_layers import bce_with_logits, mlp_apply, mlp_defs

# the batch keys the behaviour units read; they read no candidate key
HISTORY_KEYS = ("hist_item", "hist_category", "hist_len")
MASKED = -(2.0**32) + 1  # the public code's score of a padded step


def param_defs(cfg: RecSysConfig) -> Dict:
    de = cfg.embed_dim
    du = 2 * de  # behaviour-unit dim
    defs: Dict = {"tables": named_table_defs(cfg)}
    defs.update(mlp_defs("attn", 4 * du, cfg.attn_mlp_dims, prelu=False))
    tower_in = de + 4 * du  # user, candidate, Σ k, candidate · Σ k, pooled
    defs.update(mlp_defs("tower", tower_in, cfg.mlp_dims))
    return defs


def _history(params, batch, cfg, rules):
    """(units [H,L,du], Σ over the real steps [H,du], real steps [H,L]) of
    the H histories in `batch`."""
    t = params["tables"]
    it = field_lookup(t, cfg, "hist_item", batch["hist_item"], rules)
    ca = field_lookup(t, cfg, "hist_category", batch["hist_category"], rules)
    units = jnp.concatenate([it, ca], axis=-1)
    mask = jnp.arange(units.shape[1])[None] < batch["hist_len"][:, None]
    total = jnp.sum(jnp.where(mask[..., None], units, 0.0), axis=1)
    return units, total, mask


def target_attention(params, units, cand, mask, cfg):
    """units: [B,L,du]; cand: [B,du]; mask: [B,L] -> pooled [B,du]."""
    with jax.named_scope("target_attn"):
        with jax.named_scope("mlp"):
            q = jnp.broadcast_to(cand[:, None, :], units.shape)
            feats = jnp.concatenate([q, units, q - units, q * units], axis=-1)  # [B,L,4du]
            s = mlp_apply(params, "attn", feats, len(cfg.attn_mlp_dims),
                          act=jax.nn.sigmoid)[..., 0]
        with jax.named_scope("pool"):
            w = jax.nn.softmax(jnp.where(mask, s, MASKED), axis=-1)
            return jnp.sum(w[..., None] * units, axis=1)


def _logits(params, user, cand, units, total, mask, cfg):
    """Per-row logits from the rows' user and candidate embeddings and
    their histories' units, Σ k and real steps."""
    with jax.named_scope("encoder"):
        pooled = target_attention(params, units, cand, mask, cfg)
    with jax.named_scope("tower"):
        x = jnp.concatenate([user, cand, total, cand * total, pooled], axis=-1)
        return mlp_apply(params, "tower", x, len(cfg.mlp_dims))[:, 0]


def logits(params, batch, cfg: RecSysConfig, rules):
    t = params["tables"]
    with jax.named_scope("embed"):
        user = field_lookup(t, cfg, "user", batch["user"], rules)
        cand = jnp.concatenate([field_lookup(t, cfg, "item", batch["item"], rules),
                                field_lookup(t, cfg, "category", batch["category"], rules)],
                               axis=-1)
        units, total, mask = _history(params, batch, cfg, rules)
        if "hist_row" in batch:
            r = batch["hist_row"]
            units, total, mask = units[r], total[r], mask[r]
    return constrain(_logits(params, user, cand, units, total, mask, cfg), ("batch",), rules)


def loss(params, batch, cfg: RecSysConfig, rules):
    lg = logits(params, batch, cfg, rules)
    b = bce_with_logits(lg, batch["label"])
    return b, {"bce": b}


def serve(params, batch, cfg: RecSysConfig, rules):
    lg = logits(params, batch, cfg, rules)
    with jax.named_scope("tower"):
        return jax.nn.sigmoid(lg)


def retrieval(params, query, cand_ids, cfg: RecSysConfig, rules):
    """One user, N candidate items: the history gathered once, the target
    attention and the tower batched over the candidates (the N dim is
    sharded over the mesh)."""
    t = params["tables"]
    user = field_lookup(t, cfg, "user", query["user"], rules)  # [1,de]
    units, total, mask = _history(params, query, cfg, rules)  # [1,...]
    cand = jnp.concatenate([_take_rows(t["item"], cand_ids),
                            _take_rows(t["category"], query["cand_category"])], axis=-1)
    cand = constrain(cand, ("candidates", None), rules)
    N = cand.shape[0]

    def each(a):
        return jnp.broadcast_to(a, (N,) + a.shape[1:])

    scores = _logits(params, each(user), cand, each(units), each(total), each(mask), cfg)
    return constrain(scores, ("candidates",), rules)
