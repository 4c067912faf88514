"""Seeded CTR inputs: Zipf-distributed field ids and Bernoulli labels.

The benchmark keeps its own sampler so that changes to the program's data
code cannot move the yardstick.  Field cardinalities follow the same formula
as the program's synthetic Criteo/Avazu data (log-uniform sizes spanning four
orders of magnitude, renormalised to the configuration's id total); values
within a field are drawn from a truncated Zipf law by inverse CDF.
"""
from __future__ import annotations

import numpy as np


def field_cards(n_fields: int, id_total: int, card_seed: int) -> tuple[int, ...]:
    """Per-field cardinalities from the configuration's id total."""
    rng = np.random.RandomState(card_seed)
    raw = np.exp(rng.uniform(np.log(4), np.log(id_total / 4), n_fields))
    raw = raw / raw.sum() * id_total
    return tuple(int(max(c, 4)) for c in raw)


class ZipfFields:
    """Inverse-CDF sampler over ``len(cards)`` fields of global ids.

    Field ``f`` owns ids ``[offset_f, offset_f + cards[f])``; value rank ``r``
    (1-based) has probability proportional to ``r ** -a``.
    """

    def __init__(self, cards, a: float):
        self.cards = tuple(int(c) for c in cards)
        self.offsets = np.concatenate([[0], np.cumsum(self.cards)[:-1]]).astype(np.int64)
        self.n_ids = int(sum(self.cards))
        self._cdfs = []
        for card in self.cards:
            p = np.arange(1, card + 1, dtype=np.float64) ** (-a)
            cdf = np.cumsum(p)
            self._cdfs.append(cdf / cdf[-1])

    @classmethod
    def from_config(cls, data: dict) -> "ZipfFields":
        cards = field_cards(data["fields"], data["id_total"], data["card_seed"])
        return cls(cards, data["zipf_a"])

    def sample(self, rng: np.random.Generator, rows: int) -> np.ndarray:
        """int32 [rows, fields] global ids."""
        out = np.empty((rows, len(self.cards)), np.int32)
        for f, cdf in enumerate(self._cdfs):
            u = rng.random(rows)
            local = np.searchsorted(cdf, u, side="right")
            np.minimum(local, len(cdf) - 1, out=local)
            out[:, f] = local + self.offsets[f]
        return out


def labels(rng: np.random.Generator, rows: int, p: float) -> np.ndarray:
    """float32 [rows] Bernoulli(p) click labels."""
    return (rng.random(rows) < p).astype(np.float32)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent host generator per (seed, stream); seeds may exceed 32 bits."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])
