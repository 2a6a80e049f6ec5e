"""The one generator of requests, driven by a traffic file.

A traffic file gives the loop (``open``: arrivals on a schedule,
``closed``: each client sends its next request when the last one ends),
the arrival process, and the prompt and output length distributions.
The seed only orders things: lengths are the distribution's quantiles
at ``(i + 0.5) / pool`` for a fixed pool size, and arrival gaps likewise,
dealt in blocks of ``BLOCK``: each block takes one value from each of
``BLOCK`` equal strata of the sorted pool, in an order the seed shuffles.
So every seed gets the same set of sizes and the same rate, any
``BLOCK`` consecutive requests span the whole distribution, and two
seeds differ in order and in token ids alone.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    uid: int
    prompt: np.ndarray
    max_new: int


def _quantile_lengths(d: dict, n: int) -> np.ndarray:
    """Lognormal lengths at n stratified quantiles, clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.round(d["median"] * np.exp(d["sigma"] * z))
    return np.clip(v, d["min"], d["max"]).astype(np.int64)


def _quantile_gaps(rate: float, n: int) -> np.ndarray:
    """Exponential (Poisson-process) gaps at n stratified quantiles."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


BLOCK = 8


def _blocked(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Deal sorted ``values`` so that every block of BLOCK consecutive
    entries holds one from each stratum, shuffled within the block."""
    strata = np.sort(values).reshape(BLOCK, -1)          # rows: strata
    strata = np.stack([rng.permutation(row) for row in strata])
    blocks = [rng.permutation(strata[:, j]) for j in range(strata.shape[1])]
    return np.concatenate(blocks)


class Traffic:
    """Requests and arrival times for one seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = seed
        self.vocab = vocab
        n = spec["pool"]
        for part in ("prompt", "output"):
            if spec[part]["dist"] != "lognormal":
                raise ValueError(f"unknown length distribution {spec[part]['dist']!r}")
        if spec["loop"] == "open" and spec["arrival"]["process"] != "poisson":
            raise ValueError(f"unknown arrival process {spec['arrival']['process']!r}")
        if n % BLOCK:
            raise ValueError(f"pool {n} is not a multiple of {BLOCK}")
        rng = np.random.default_rng([seed, 0])
        self._prompt = _blocked(_quantile_lengths(spec["prompt"], n), rng)
        self._output = _blocked(_quantile_lengths(spec["output"], n), rng)
        if spec["loop"] == "open":
            gaps = _blocked(_quantile_gaps(spec["arrival"]["rate"], n), rng)
            self.arrivals = np.cumsum(gaps)

    @property
    def closed(self) -> bool:
        return self.spec["loop"] == "closed"

    def request(self, i: int) -> RequestSpec:
        """Request ``i`` of the stream (lengths repeat every pool)."""
        n = self.spec["pool"]
        rng = np.random.default_rng([self.seed, 1, i])
        plen = int(self._prompt[i % n])
        return RequestSpec(uid=i, prompt=rng.integers(0, self.vocab, plen),
                           max_new=int(self._output[i % n]))

    def initial(self, clients: int) -> list[RequestSpec]:
        """The closed loop's first request per client, met part way
        through: its remaining output is drawn from the residual life of
        the output length (a request found at a random moment), at
        stratified quantiles shuffled by the seed, so that retirements
        are staggered from the first tick and every seed starts from the
        same set of remaining lengths."""
        lengths = np.sort(_quantile_lengths(self.spec["output"], self.spec["pool"]))
        x = np.arange(1, lengths[-1] + 1)
        # P(R <= r) = sum_{x <= r} P(L >= x) / E[L]
        cdf = np.cumsum(1.0 - np.searchsorted(lengths, x) / len(lengths))
        cdf /= cdf[-1]
        q = (np.arange(clients) + 0.5) / clients
        remaining = x[np.searchsorted(cdf, q)]
        rng = np.random.default_rng([self.seed, 2])
        out = []
        for c, rem in enumerate(rng.permutation(remaining)):
            r = self.request(c)
            r.max_new = int(rem)
            out.append(r)
        return out

    def due_before(self, t: float) -> int:
        """How many arrivals fall at or before ``t`` (open loop)."""
        return int(np.searchsorted(self.arrivals, t, side="right"))
