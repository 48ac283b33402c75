"""The one traffic generator: reads a mix file (``traffic/<name>.json``)
and draws operations for a closed loop of clients.

A mix file gives ``clients`` and ``max_batch``, the share of each kind of
operation (``mix``), how keys are drawn (``keys``: ``zipfian`` with
``theta``, or ``uniform``, over the loaded records; inserts always take
fresh keys) and ``warmup_ticks``, the ticks of the mix served before the
window opens. As in YCSB, every operation draws its kind from the mix,
then its key and value.

The zipfian ranks follow YCSB's weights p(r) ~ 1/(r+1)^theta by inversion
of the exact CDF over the loaded records; the records are in random order,
which scrambles rank to key as YCSB's scrambled zipfian does. Copied from
``src/repro/workloads/ycsb.py:zipfian_ranks`` and ``MIXES``, with the CDF
built once instead of per call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from .data import INSERT, WRITES, rng_for

KINDS = ("read", "update", "insert", "delete", "rmw")
_CHUNK = 1 << 16


@dataclasses.dataclass
class Mix:
    clients: int
    max_batch: int
    mix: Dict[str, float]          # kind -> share, shares summing to 1
    distribution: str
    theta: float
    warmup_ticks: int
    raw: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_json(cls, d: dict) -> "Mix":
        mix = {k: float(v) for k, v in d["mix"].items() if float(v) > 0}
        unknown = set(mix) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown operation kinds {sorted(unknown)}")
        total = sum(mix.values())
        keys = d.get("keys", {})
        return cls(clients=int(d["clients"]), max_batch=int(d["max_batch"]),
                   mix={k: mix[k] / total for k in sorted(mix)},
                   distribution=keys.get("distribution", "uniform"),
                   theta=float(keys.get("theta", 0.99)),
                   warmup_ticks=int(d.get("warmup_ticks", 16)), raw=d)


class KeyDraw:
    """Ranks over the loaded records, drawn in chunks."""

    def __init__(self, rng: np.random.Generator, n: int, distribution: str,
                 theta: float):
        self.rng, self.n = rng, n
        self.cdf = None
        if distribution == "zipfian":
            w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
        elif distribution != "uniform":
            raise ValueError(f"unknown key distribution {distribution!r}")
        self._buf = np.zeros(0, np.int64)
        self._i = 0

    def draw(self, size: int) -> np.ndarray:
        """Ranks in [0, n)."""
        if self.cdf is None:
            return self.rng.integers(0, self.n, size)
        return np.searchsorted(self.cdf, self.rng.random(size)).clip(
            0, self.n - 1)

    def next(self) -> int:
        if self._i >= self._buf.size:
            self._buf, self._i = self.draw(_CHUNK), 0
        self._i += 1
        return int(self._buf[self._i - 1])


class Generator:
    """Draws operations: ``draw`` takes the kind from the mix, ``next`` and
    ``batch`` are of a given kind."""

    def __init__(self, mix: Mix, seed: int, keys: np.ndarray,
                 spare: np.ndarray, stream: int = 1):
        self.mix = mix
        self.keys, self.spare = keys, spare
        rng = rng_for(seed, stream)
        self.ranks = KeyDraw(rng, keys.size, mix.distribution, mix.theta)
        self.vals = rng
        self._vbuf = np.zeros(0, np.uint32)
        self._vi = 0
        self.next_fresh = 0
        self._kinds = list(mix.mix)
        self._kcdf = np.cumsum([mix.mix[k] for k in self._kinds])
        self._kbuf = np.zeros(0, np.int64)
        self._ki = 0

    def value(self) -> int:
        if self._vi >= self._vbuf.size:
            self._vbuf = self.vals.integers(1, 2**32, _CHUNK,
                                            dtype=np.uint64).astype(np.uint32)
            self._vi = 0
        self._vi += 1
        return int(self._vbuf[self._vi - 1])

    def next(self, kind: str):
        """(kind, key, value) of the next operation of this kind."""
        if kind == INSERT:
            if self.next_fresh >= self.spare.size:
                raise RuntimeError("the configuration's spare keys are "
                                   "spent: raise spare_keys")
            key = int(self.spare[self.next_fresh])
            self.next_fresh += 1
            return kind, key, self.value()
        key = int(self.keys[self.ranks.next()])
        return kind, key, (self.value() if kind in WRITES else 0)

    def draw(self) -> tuple:
        """(kind, key, value) of the next operation, its kind drawn from
        the mix."""
        if self._ki >= self._kbuf.size:
            self._kbuf = np.searchsorted(
                self._kcdf, self.vals.random(_CHUNK), side="right").clip(
                0, len(self._kinds) - 1)
            self._ki = 0
        self._ki += 1
        return self.next(self._kinds[self._kbuf[self._ki - 1]])

    def batch(self, kind: str, n: int) -> List[tuple]:
        return [self.next(kind) for _ in range(n)]

