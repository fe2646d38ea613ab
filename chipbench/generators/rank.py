"""Ranking requests: one user of a seeded pool, that user's behaviour
history, and candidates to score.

Users are laid out as the paper's Section V.A Taobao log (the layout of
`repro.data.synthetic.TaobaoWorld`, copied so that program edits cannot
move it): items have categories, users have latent category preferences,
a history is the `seq_len` items a user prefers out of `4 * seq_len`
drawn, truncated to a length in `hist_len`. A share `from_history` of a
request's candidates comes from the user's history, the rest is uniform
over the items.

Parameters of a mix file:
  arrivals      "poisson": open loop at mean rate `rate_per_s`;
                "saturated": every request is due when the window opens.
  burst         optional, poisson only: {"factor": f, "seconds": d,
                "every_s": p}, the rate is f times the mean for d seconds
                out of every p, and lower between, so the mean holds.
  candidates    a count, or {"lognormal_median": m, "sigma": s, "min": a,
                "max": b}: counts from the lognormal's quantiles, clipped.
  users, distinct_requests, from_history, hist_len ([lo, hi]), pref_dim.
Every seed gets the same multiset of history lengths, candidate counts
and gaps between arrivals, in its own order: seeds change the order of the
work, not its amount.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from chipbench.traffic import Traffic, poisson_due

KEYS = {"generator", "arrivals", "rate_per_s", "burst", "users", "distinct_requests",
        "candidates", "from_history", "hist_len", "pref_dim"}
ARRIVALS = ("poisson", "saturated")


def check_mix(mix: dict, seq_len: int) -> None:
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"arrivals must be one of {ARRIVALS}, not {mix['arrivals']!r}")
    if mix["arrivals"] == "poisson" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError("poisson arrivals need a rate_per_s above 0")
    if "burst" in mix and (mix["arrivals"] != "poisson"
                           or mix["burst"]["factor"] * mix["burst"]["seconds"]
                           > mix["burst"]["every_s"]):
        raise ValueError(f"burst {mix['burst']} does not fit a poisson mix's mean rate")
    lo, hi = mix["hist_len"]
    if not 1 <= lo <= hi <= seq_len:
        raise ValueError(f"hist_len {mix['hist_len']} is not a range of lengths up to {seq_len}")


def counts(spec, n: int, rng) -> np.ndarray:
    """Candidates of each of `n` contents."""
    if isinstance(spec, int):
        return np.full(n, spec)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    c = np.rint(spec["lognormal_median"] * np.exp(spec["sigma"] * z))
    return rng.permutation(np.clip(c, spec["min"], spec["max"]).astype(np.int64))


def bursty(due: np.ndarray, burst: dict) -> np.ndarray:
    """Moves arrivals at a steady rate to a rate `factor` times as high for
    `seconds` out of every `every_s`, and lower between, same mean."""
    f, d, p = burst["factor"], burst["seconds"], burst["every_s"]
    periods = np.arange(int(due[-1] // p) + 2 if len(due) else 1) * p
    t = np.stack([periods, periods + d], axis=1).ravel()
    # what a steady rate would have brought by each time t: a period brings
    # f * d of its p in its burst, the rest after it
    steady = np.stack([periods, periods + f * d], axis=1).ravel()
    return np.interp(due, steady, t)


def make(mix: dict, cfg: dict, seed: int, seconds: float) -> Traffic:
    L = cfg["seq_len"]
    check_mix(mix, L)
    n_items, n_cats = cfg["items"], cfg["categories"]
    P, N = mix["users"], mix["distinct_requests"]
    rng = np.random.default_rng(seed)

    item_cat = rng.integers(0, n_cats, n_items)
    user = rng.choice(cfg["users"], P, replace=False)
    pref = rng.normal(size=(P, mix["pref_dim"])).astype(np.float32)
    cat_vec = rng.normal(size=(n_cats, mix["pref_dim"])).astype(np.float32)
    drawn = rng.integers(0, n_items, (P, 4 * L))
    aff = np.einsum("ud,ukd->uk", pref, cat_vec[item_cat[drawn]])
    hist = np.take_along_axis(drawn, np.argsort(-aff, axis=1)[:, :L], axis=1)
    lo, hi = mix["hist_len"]
    hist_len = rng.permutation(lo + np.arange(P) % (hi - lo + 1))
    hist = np.where(np.arange(L)[None] < hist_len[:, None], hist, 0)

    req_pool = rng.integers(0, P, N)
    n = counts(mix["candidates"], N, rng)
    n_hist = np.rint(n * mix["from_history"]).astype(np.int64)
    start = np.concatenate([[0], np.cumsum(n)])
    # each content's candidates: n_hist from its user's history, then the rest uniform
    from_hist = np.arange(start[-1]) - np.repeat(start[:-1], n) < np.repeat(n_hist, n)
    pool_of = np.repeat(req_pool, n_hist)
    cand = np.empty(start[-1], np.int64)
    cand[from_hist] = hist[pool_of, rng.integers(0, hist_len[pool_of])]
    cand[~from_hist] = rng.integers(0, n_items, int(np.sum(n - n_hist)))

    due = None
    if mix["arrivals"] == "poisson":
        due = poisson_due(mix["rate_per_s"], seconds, rng)
        if "burst" in mix:
            due = bursty(due, mix["burst"])  # never later than the steady time
    i32 = np.int32
    return Traffic(user=user.astype(i32), hist_item=hist.astype(i32),
                   hist_category=item_cat[hist].astype(i32),
                   hist_len=hist_len.astype(i32), req_pool=req_pool.astype(i32),
                   cand_start=start.astype(np.int64), cand_item=cand.astype(i32),
                   cand_category=item_cat[cand].astype(i32), due=due)
