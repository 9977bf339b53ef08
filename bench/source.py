"""The benchmark's traffic: one general generator that every mix file feeds.

A configuration that names another generator under ``traffic_source`` is
fed by ``bench/sources/<name>.py`` instead; its ``traffic(cfg, mix,
intervals, seed)`` returns the key arrays as :func:`traffic` does. Every mix
(``bench/traffic/<mix>.json``) gives the number of warm-up intervals,
``layout_seed`` and ``pool_rate``: the source stays ahead of the engine,
with a pool of intervals sized for that many tuples per second. Its other
keys reach the generator as ``Mix.params``, as the file gives them.

This generator takes the key domain ``K``, the Zipf exponent ``z``, the
tasks, the hash seed and the tuples per interval from the configuration,
and the fluctuation rate ``f`` and ``max_swaps`` from the mix.

The key frequencies follow the paper's synthetic generator (arXiv:1610.05121
Sec. V): Zipf(``z``) over ``K`` key ids, the ranks laid over the ids by a
seeded permutation; at each new interval frequencies are swapped between
pairs of keys on different task instances until the workload of some
instance has changed by ``|L_i(d) - L_{i-1}(d)| / L_{i-1}(d) >= f``. Two
things keep it open loop and steady:

* the instance of a key is the hash the stage starts from,
  ``fmix32(k ^ hash_seed) % tasks``, never the live routing table, so the
  traffic cannot adapt to the system it is fed to;
* the frequencies of every interval (the layout and every swap) come from
  the mix's ``layout_seed``, and the run's seed draws only the tuples from
  them (their order and the counts' sampling noise). Every seed then offers
  the same workload changes at the same intervals.

The swaps are drawn in rounds of disjoint pairs (a seeded permutation of the
keys, taken two by two), so a round's swaps commute and the first one that
reaches ``f`` is found from a running sum of the load changes.
"""

from __future__ import annotations

import dataclasses
import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import List

import numpy as np

from reference import hashed

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix, as its file states it."""

    name: str
    warmup_intervals: int       # intervals run in set-up, before the window
    layout_seed: int            # seed of the layout and of every swap
    pool_rate: float            # tuples/s the pool is sized for
    params: dict                # the file's other keys, for the generator

    @classmethod
    def load(cls, name: str) -> "Mix":
        path = TRAFFIC_DIR / f"{name}.json"
        raw = json.loads(path.read_text())
        fields = {f.name for f in dataclasses.fields(cls)} - {"params"}
        mix = cls(name=name,
                  params={k: v for k, v in raw.items() if k not in fields},
                  **{k: v for k, v in raw.items() if k in fields})
        if mix.pool_rate <= 0:
            raise ValueError(f"{path}: pool_rate must be positive")
        return mix

    def window_intervals(self, seconds: float, tuples: int) -> int:
        """Intervals the window uses: enough to keep a source at
        ``pool_rate`` ahead for the window and one interval more."""
        return int(np.ceil(seconds * self.pool_rate / tuples)) + 1


def fluctuate(freq: np.ndarray, dest: np.ndarray, tasks: int, f: float,
              max_swaps: int, rng: np.random.Generator) -> int:
    """Swap ``freq`` in place between keys on different instances until
    some instance's load has changed by a share ``f`` of what it was, or
    ``max_swaps`` pairs were tried; returns the swaps made."""
    old = np.maximum(np.bincount(dest, weights=freq, minlength=tasks), 1e-300)
    drift = np.zeros(tasks)
    tried = made = 0
    half = freq.size // 2
    while tried < max_swaps:
        perm = rng.permutation(freq.size)
        n = min(half, max_swaps - tried)
        tried += n
        i, j = perm[:2 * n:2], perm[1:2 * n:2]
        keep = dest[i] != dest[j]
        i, j = i[keep], j[keep]
        delta = freq[j] - freq[i]
        steps = np.zeros((i.size, tasks))
        rows = np.arange(i.size)
        steps[rows, dest[i]] = delta
        steps[rows, dest[j]] = -delta
        path = drift + np.cumsum(steps, axis=0)
        hit = np.nonzero(np.max(np.abs(path) / old, axis=1) >= f)[0]
        m = int(hit[0]) + 1 if hit.size else i.size
        freq[i[:m]], freq[j[:m]] = freq[j[:m]], freq[i[:m]]
        made += m
        if hit.size:
            break
        if i.size:
            drift = path[-1]
    return made


def frequencies(domain: int, z: float, intervals: int, *, f: float,
                tasks: int, hash_seed: int, max_swaps: int,
                layout_seed: int) -> np.ndarray:
    """``(intervals, domain)`` key probabilities: Zipf(``z``) laid over the
    key ids, then ``f``-fluctuated at every interval after the first."""
    rng = np.random.default_rng(int(layout_seed))
    freq = np.arange(1, domain + 1, dtype=np.float64) ** -z
    freq = freq[rng.permutation(domain)]
    freq /= freq.sum()
    dest = hashed(domain, tasks, hash_seed)
    out = np.empty((intervals, domain))
    for i in range(intervals):
        if i and f > 0:
            fluctuate(freq, dest, tasks, f, max_swaps, rng)
        out[i] = freq
    return out


def draw(probs: np.ndarray, tuples: int, seed: int,
         threads: int = 8) -> List[np.ndarray]:
    """Per interval, ``tuples`` int64 key ids drawn from its probabilities;
    every interval draws from its own child of ``seed``."""
    seqs = np.random.SeedSequence(int(seed)).spawn(probs.shape[0])
    top = probs.shape[1] - 1

    def one(i: int) -> np.ndarray:
        cdf = np.cumsum(probs[i])
        u = np.random.default_rng(seqs[i]).random(tuples) * cdf[-1]
        keys = np.searchsorted(cdf, u, side="right")
        return np.minimum(keys, top).astype(np.int64)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(one, range(probs.shape[0])))


def traffic(cfg: dict, mix: Mix, intervals: int, seed: int
            ) -> List[np.ndarray]:
    """The key arrays of ``intervals`` intervals of ``mix`` under ``cfg``."""
    probs = frequencies(cfg["domain"], cfg["zipf"], intervals,
                        f=mix.params["f"], tasks=cfg["tasks"],
                        hash_seed=cfg["hash_seed"],
                        max_swaps=mix.params["max_swaps"],
                        layout_seed=mix.layout_seed)
    return draw(probs, cfg["tuples"], seed)
