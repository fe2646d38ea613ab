"""Expert-parallel MoE dispatch via shard_map + all_to_all (production path).

GSPMD cannot partition data-dependent gather/scatter dispatch well — the
auto-sharded path (models/moe.py) compiles with involuntary full
rematerialization: ~TB-scale collectives per step (measured; see
EXPERIMENTS.md §Perf). This module is the explicit scheme every production
MoE system uses:

  local top-k routing
   -> sort entries by target expert group (model-axis device)
   -> capacity-bounded send buffers            [n_groups, C_pair, D]
   -> all_to_all over the expert axis          (the ONLY big collective)
   -> local per-expert grouping (second sort)  [E_loc, C_e, D]
   -> batched expert FFN (weights gathered over the dp axes, FSDP-style)
   -> inverse scatter -> all_to_all back -> weighted combine.

Everything is differentiable (a2a/all_gather/scatter all have transposes),
runs under jax.checkpoint inside the layer scan, and degenerates gracefully
on a 1-device mesh (smoke tests compare it against the dense oracle).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import pspec


def _sort_dispatch(keys: jax.Array, n_bins: int, capacity: int):
    """entries -> (slot, kept): slot = bin*capacity + rank within bin
    (rank >= capacity dropped). keys: [N] int32 in [0, n_bins)."""
    order = jnp.argsort(keys)
    sorted_keys = keys[order]
    first = jnp.searchsorted(sorted_keys, sorted_keys, side="left")
    rank = jnp.arange(keys.shape[0]) - first
    kept = rank < capacity
    slot_sorted = jnp.where(kept, sorted_keys * capacity + rank, n_bins * capacity)
    # scatter slot back to entry order
    slot = jnp.zeros_like(slot_sorted).at[order].set(slot_sorted)
    kept_e = jnp.zeros(keys.shape, bool).at[order].set(kept)
    return slot, kept_e


def _capacity(n: int, bins: int, cf: float) -> int:
    return max(4, math.ceil(n / bins * cf))


def moe_ffn_ep(x, layer_params, cfg, rules) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN. x: [B, S, D] (global). Returns (out, aux)."""
    ep_axis = rules.get("experts")
    dp_axes = rules.get("batch") or ()
    if isinstance(dp_axes, str):
        dp_axes = (dp_axes,)
    if ep_axis is None:
        from repro.models.moe import moe_ffn

        return moe_ffn(x, layer_params, cfg)
    seq_axis = rules.get("seq")

    x_spec = P(dp_axes if dp_axes else None, seq_axis, None)
    w_spec = P(ep_axis, dp_axes if dp_axes else None, None)
    r_spec = P(None, None)
    out_specs = (x_spec, P())

    has_shared = "shared_gate" in layer_params
    shared_specs = {}
    if has_shared:
        # shared expert: dense FFN, weights FSDP over dp axes on dim 0
        shared_specs = {
            "shared_gate": P(dp_axes if dp_axes else None, None),
            "shared_up": P(dp_axes if dp_axes else None, None),
            "shared_down": P(dp_axes if dp_axes else None, None),
        }
    in_specs = (
        x_spec,
        {
            "router": r_spec,
            "w_gate": w_spec,
            "w_up": w_spec,
            "w_down": w_spec,
            **shared_specs,
        },
    )

    E, K, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor

    def body(x_loc, p_loc):
        n_groups = jax.lax.axis_size(ep_axis)
        E_loc = E // n_groups
        B_loc, S_loc, D = x_loc.shape
        T_loc = B_loc * S_loc
        xt = x_loc.reshape(T_loc, D)

        # ---- routing (router weights replicated) ----
        logits = jnp.einsum("td,de->te", xt, p_loc["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, K)  # [T_loc, K]
        gate = gate / jnp.clip(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), 1), 0)
        aux = E * jnp.sum(me * ce)
        if dp_axes or ep_axis:
            aux = jax.lax.pmean(aux, tuple(dp_axes) + (ep_axis,))

        # ---- stage 1: entries -> expert-group send buffers ----
        flat_e = eidx.reshape(-1)  # [T_loc*K]
        group = flat_e // E_loc
        C_pair = _capacity(T_loc * K, n_groups, cf)
        slot, kept = _sort_dispatch(group, n_groups, C_pair)
        tok = jnp.arange(T_loc * K) // K

        send_x = jnp.zeros((n_groups * C_pair, D), x_loc.dtype)
        send_x = send_x.at[slot].set(xt[tok], mode="drop")
        send_e = jnp.full((n_groups * C_pair,), -1, jnp.int32)
        send_e = send_e.at[slot].set((flat_e - group * E_loc).astype(jnp.int32), mode="drop")

        # ---- all_to_all to expert owners ----
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_groups, C_pair, D), ep_axis, 0, 0, tiled=False
        ).reshape(n_groups * C_pair, D)
        recv_e = jax.lax.all_to_all(
            send_e.reshape(n_groups, C_pair), ep_axis, 0, 0, tiled=False
        ).reshape(n_groups * C_pair)

        # ---- stage 2: group received tokens by local expert ----
        R = n_groups * C_pair
        keys2 = jnp.where(recv_e >= 0, recv_e, E_loc)  # padding -> drop bin
        C_e = _capacity(R, E_loc, cf)
        slot2, kept2 = _sort_dispatch(keys2, E_loc, C_e)
        slot2 = jnp.where(recv_e >= 0, slot2, E_loc * C_e)

        buf = jnp.zeros((E_loc * C_e, D), x_loc.dtype)
        buf = buf.at[slot2].set(recv_x, mode="drop")
        buf = buf.reshape(E_loc, C_e, D)

        # ---- expert FFN; weights FSDP-gathered over the dp axes ----
        def full(w):
            if dp_axes:
                return jax.lax.all_gather(w, tuple(dp_axes), axis=1, tiled=True)
            return w

        g = jnp.einsum("ecd,edf->ecf", buf, full(p_loc["w_gate"]))
        u = jnp.einsum("ecd,edf->ecf", buf, full(p_loc["w_up"]))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, full(p_loc["w_down"]))
        out_buf = out_buf.reshape(E_loc * C_e, D)

        # ---- inverse: buffer -> recv layout -> a2a back -> combine ----
        back = jnp.where(
            (slot2 < E_loc * C_e)[:, None],
            jnp.take(out_buf, jnp.clip(slot2, 0, E_loc * C_e - 1), axis=0),
            0.0,
        )  # [R, D]
        ret = jax.lax.all_to_all(
            back.reshape(n_groups, C_pair, D), ep_axis, 0, 0, tiled=False
        ).reshape(n_groups * C_pair, D)

        entry_out = jnp.where(
            kept[:, None],
            jnp.take(ret, jnp.clip(slot, 0, n_groups * C_pair - 1), axis=0),
            0.0,
        )  # [T_loc*K, D]
        out = jnp.sum(
            entry_out.reshape(T_loc, K, D) * gate[..., None].astype(x_loc.dtype), axis=1
        )

        if has_shared:

            def full0(w):  # shared weights FSDP-sharded on dim 0
                if dp_axes:
                    return jax.lax.all_gather(w, tuple(dp_axes), axis=0, tiled=True)
                return w

            sg, su, sd = (full0(p_loc[k]) for k in
                          ("shared_gate", "shared_up", "shared_down"))
            hg = jax.nn.silu((xt @ sg).astype(jnp.float32)).astype(x_loc.dtype)
            out = out + (hg * (xt @ su)) @ sd

        return out.reshape(B_loc, S_loc, D), aux

    mesh = rules.get("__mesh__")
    # check_vma=False: under some layouts (e.g. TP train, seq unsharded) the
    # router aux is invariant along the expert axis and the VMA checker
    # rejects the (correct) pmean over it.
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    moe_in = {k: layer_params[k] for k in
              ("router", "w_gate", "w_up", "w_down") if k in layer_params}
    if has_shared:
        moe_in.update({k: layer_params[k] for k in
                       ("shared_gate", "shared_up", "shared_down")})
    return fn(x, moe_in)
