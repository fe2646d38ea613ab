"""The requests of a run, and the lookup of the generator that makes them.

A mix is a file of parameters, `chipbench/traffic/<mix>.json`. Its
`generator` key names the module `chipbench/generators/<generator>.py`,
whose `make(mix, cfg, seed, seconds) -> Traffic` makes every request of
the run from `--seed` before the window opens. A mix that no generator
here can make comes with a generator file of its own; no file that is
there changes.

A run serves requests 0, 1, 2, ...; request k asks for content
k % (number of contents), so a run never runs out of requests. Each
content is one user of a pool, with that user's history, and its own
number of candidates to score.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Optional

import numpy as np

GENERATORS = Path(__file__).resolve().parent / "generators"


@dataclasses.dataclass
class Traffic:
    user: np.ndarray            # [P] user id of each pool user
    hist_item: np.ndarray       # [P, L]
    hist_category: np.ndarray   # [P, L]
    hist_len: np.ndarray        # [P]
    req_pool: np.ndarray        # [N] pool user of each content
    cand_start: np.ndarray      # [N + 1] content n scores candidates cand_start[n]:cand_start[n + 1]
    cand_item: np.ndarray       # [T]
    cand_category: np.ndarray   # [T]
    due: Optional[np.ndarray]   # [M] seconds after the window opens; None = saturated
    count: np.ndarray = dataclasses.field(init=False, repr=False)  # [N] candidates

    def __post_init__(self):
        self.count = np.diff(self.cand_start)

    @property
    def contents(self) -> int:
        return len(self.req_pool)

    def content(self, first: int, stop: int) -> np.ndarray:
        return np.arange(first, stop) % self.contents

    def rows(self, first: int, stop: int) -> int:
        """Candidates of requests [first, stop)."""
        return int(self.count[self.content(first, stop)].sum())

    def fill(self, first: int, stop: int, max_rows: int) -> int:
        """The end of the longest run of whole requests from `first`, at
        most to `stop`, whose candidates fit `max_rows` rows."""
        n = self.count[self.content(first, min(stop, first + max_rows))]
        return first + int(np.searchsorted(np.cumsum(n), max_rows, side="right"))

    def row_index(self, first: int, stop: int) -> tuple:
        """(pool user, candidate) of each row of requests [first, stop), in
        order: a request's candidates one after the other."""
        c = self.content(first, stop)
        n = self.count[c]
        start = np.repeat(self.cand_start[c] - (np.cumsum(n) - n), n)
        return np.repeat(self.req_pool[c], n), start + np.arange(len(start))


def poisson_due(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds) at mean rate `rate`: the quantiles of an
    exponential gap of mean 1/rate (the gaps of
    `repro.core.serving.engine.poisson_arrivals`), shuffled by `rng`, summed.
    Every seed gets the same multiset of gaps, so that seeds change the
    order of the work and not its amount."""
    n = int(np.ceil(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    return due[due < seconds]


def make_traffic(mix: dict, cfg: dict, seed: int, seconds: float) -> Traffic:
    """Every request of a run of `mix` on configuration `cfg`."""
    path = GENERATORS / f"{mix['generator']}.py"
    if not path.is_file():
        raise ValueError(f"no traffic generator {mix['generator']!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_generator_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(mix, cfg, seed, seconds)
