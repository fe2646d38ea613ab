"""Uniform recsys model API: dispatch by cfg.interaction."""
from __future__ import annotations

from repro.configs.base import RecSysConfig
from repro.models.recsys import autoint, dien, din, fm, taobao_ssa

_MODULES = {
    "fm": fm,
    "self_attn": autoint,
    "target_attn": din,
    "augru": dien,
    "self_attn_seq": taobao_ssa,
}


def module_for(cfg: RecSysConfig):
    return _MODULES[cfg.interaction]


def history_keys(cfg) -> tuple:
    """The batch keys that make up the family's candidate-independent
    history, which its `serve` accepts once per distinct history beside
    `hist_row`; () where the history is pooled per candidate or absent."""
    return getattr(module_for(cfg), "HISTORY_KEYS", ())


def param_defs(cfg):
    return module_for(cfg).param_defs(cfg)


def loss(params, batch, cfg, rules):
    return module_for(cfg).loss(params, batch, cfg, rules)


def serve(params, batch, cfg, rules):
    return module_for(cfg).serve(params, batch, cfg, rules)


def retrieval(params, query, cand_ids, cfg, rules):
    return module_for(cfg).retrieval(params, query, cand_ids, cfg, rules)
