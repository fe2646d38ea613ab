"""DIN as the benchmark drives it: the program's jitted serve step
(`repro.launch.serve.make_serve_step`, i.e. `api.serve` -> `din.serve`) on
the program's pointwise batch, one row per candidate, and the plain
reference beside it.

The weights are the benchmark's, made from the seed on the device
(`reference.make_weights`), and handed to the program as they are.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import din as ref

ROW_BLOCK = 8192  # candidate rows per reference call: [8192, 100, 144] f32 features, 472 MB
CPU_CUT = dict(users=2000, items=3000, categories=300)
CPU_WIDTHS = dict(seq_len=20, embed_dim=8, attn=[16, 8], tower=[24, 8])


def program_config(cfg: dict):
    """The program's RecSysConfig holding the sizes of `cfg`."""
    from repro.configs.base import FieldSpec
    from repro.configs.din import config

    L = cfg["seq_len"]
    fields = (FieldSpec("user", cfg["users"]),
              FieldSpec("item", cfg["items"]),
              FieldSpec("category", cfg["categories"]),
              FieldSpec("hist_item", cfg["items"], multi_hot=L, shares="item"),
              FieldSpec("hist_category", cfg["categories"], multi_hot=L,
                        shares="category"))
    return dataclasses.replace(config(), fields=fields, embed_dim=cfg["embed_dim"], seq_len=L,
                               attn_mlp_dims=tuple(cfg["attn"]), mlp_dims=tuple(cfg["tower"]))


class Model:
    def __init__(self, cfg: dict):
        from repro.launch.serve import make_serve_step, recsys_rules
        from repro.models.common import is_def
        from repro.models.recsys import api

        self.cfg = cfg
        pcfg = program_config(cfg)
        defs = jax.tree.map(lambda d: tuple(d.shape), api.param_defs(pcfg), is_leaf=is_def)
        if defs != ref.weight_shapes(cfg):
            raise ValueError(f"program parameters {defs} differ from the reference's")
        self.step = make_serve_step(pcfg, recsys_rules())
        self.peak = cfg["peak"]

    def params(self, key):
        """The program's parameters from `key`, built in one jitted call."""
        return jax.jit(lambda k: ref.make_weights(self.cfg, k))(key)

    def batch(self, tr, first: int, stop: int, rows: int) -> dict:
        """Requests [first, stop) as `rows` pointwise rows, zero-padded."""
        pool, cand = tr.row_index(first, stop)
        out = {"user": tr.user[pool], "item": tr.cand_item[cand],
               "category": tr.cand_category[cand],
               "hist_item": tr.hist_item[pool], "hist_category": tr.hist_category[pool],
               "hist_len": tr.hist_len[pool]}
        return {k: np.pad(v, [(0, rows - len(pool))] + [(0, 0)] * (v.ndim - 1))
                for k, v in out.items()}

    def request_flops(self, n_cand: int) -> int:
        return ref.request_flops(self.cfg, n_cand)

    def reference(self, key, tr, pool: np.ndarray, cand: np.ndarray,
                  control: bool = False) -> np.ndarray:
        """Reference probabilities of the rows (pool user `pool`, candidate
        `cand`). With `control`, the reference in bfloat16, one precision
        step lower than the configuration's float32."""
        cfg = self.cfg

        def build(k):
            w = ref.make_weights(cfg, k)
            return jax.tree.map(lambda x: x.astype(jnp.bfloat16), w) if control else w

        with jax.default_matmul_precision("highest"):
            w = jax.jit(build)(key)
            users, h = np.unique(pool, return_inverse=True)  # each history once
            hist = jax.jit(ref.history)(w, tr.hist_item[users], tr.hist_category[users],
                                        tr.hist_len[users])
            score = jax.jit(lambda w, hist, h, u, ci, cc: ref.scores(
                w, *(a[h] for a in hist), u, ci, cc, cfg))
            rb = min(ROW_BLOCK, len(pool))
            rows = (h, tr.user[pool], tr.cand_item[cand], tr.cand_category[cand])
            out = [np.asarray(score(w, hist, *(_block(a, i, rb) for a in rows)))
                   for i in range(0, len(pool), rb)]
        return np.concatenate(out)[:len(pool)].astype(np.float32)


def _block(a: np.ndarray, i: int, n: int) -> np.ndarray:
    """Rows [i, i + n) of `a`, zero-padded to n rows."""
    b = a[i:i + n]
    return np.pad(b, [(0, n - len(b))] + [(0, 0)] * (b.ndim - 1))
