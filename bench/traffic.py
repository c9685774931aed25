"""The one traffic generator: reads a mix's parameters from its data file.

Every seed gets the same work in the same order; the seed draws the
prompt tokens (and the benchmark the weights).  Prompt and output
lengths are stratified quantiles of the mix's distributions (block by
block, so every prefix of whole blocks holds the same multiset), and
open-loop inter-arrival gaps are stratified quantiles of the exponential
distribution, scaled so the window's arrivals end at the same instant.
A fixed generator shuffles them: in a closed loop the order decides
which requests the window holds, so an order drawn from the seed would
change the work from seed to seed (tok_s spread 3.5-3.8 % between seeds
against 0-1.1 % between two runs of one seed, on one TPU v5e chip).

Parameters (``bench/traffic/<name>.json``):

  loop              "open" (Poisson schedule) or "closed" (clients)
  rate_per_s        open loop: arrivals per second
  clients_per_slot  closed loop: clients per engine slot
  prompt, output    {"dist": "uniform"|"lognormal", "min", "max"
                     [, "median", "sigma"]} in tokens
  block             stratification block (requests), default 16
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Req:
    uid: int
    arrival: float  # seconds after the window opened
    prompt_len: int
    out_len: int
    measured: bool = True


def quantile(dist, u):
    """The ``u``-quantile (0 < u < 1) of a length distribution, as an
    integer in [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        return min(hi, lo + int(u * (hi - lo + 1)))
    if dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(hi, max(lo, round(v))))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def stratified(dist, n):
    """The n stratified quantiles (i + 0.5) / n of ``dist``."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


class Traffic:
    def __init__(self, spec, seed, seconds, vocab):
        self.spec = spec
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.seed = int(seed) % (1 << 64)
        self.block = int(spec.get("block", 16))
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self._order = np.random.default_rng(0x7AFF1C)  # not the seed
        self._sizes = []  # closed loop: (prompt_len, out_len) by issue

    # ----------------------------------------------------------- lengths
    def _multiset(self, n):
        """One stratified multiset of ``n`` (prompt, output) lengths, each
        axis shuffled."""
        p = stratified(self.spec["prompt"], n)
        o = stratified(self.spec["output"], n)
        self._order.shuffle(p)
        self._order.shuffle(o)
        return list(zip(p, o))

    def sizes(self, i):
        """Closed loop: the lengths of the ``i``-th request issued."""
        while len(self._sizes) <= i:
            self._sizes.extend(self._multiset(self.block))
        return self._sizes[i]

    # ------------------------------------------------------ open loop
    def window_requests(self):
        """Open loop: the requests due in the window, arrivals from 0."""
        return self._open(0, 0.0, measured=True)

    def filler_requests(self, start_uid):
        """Open loop: the same process continued past the window (not
        measured), one window's worth at a time; yields forever."""
        uid, t = start_uid, self.seconds
        while True:
            reqs = self._open(uid, t, measured=False)
            yield from reqs
            uid += len(reqs)
            t += self.seconds

    def _open(self, uid0, t0, measured):
        """One window of arrivals from ``t0``: stratified exponential gaps
        scaled to the window, shuffled."""
        n = max(1, round(self.spec["rate_per_s"] * self.seconds))
        g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        self._order.shuffle(g)
        scale = self.seconds / sum(g)
        out, t = [], t0
        for i, (pl, ol) in enumerate(self._multiset(n)):
            out.append(Req(uid0 + i, t, pl, ol, measured))
            t += g[i] * scale
        return out

    # ---------------------------------------------------- closed loop
    def clients(self, capacity):
        return int(self.spec["clients_per_slot"]) * capacity

    def closed_request(self, uid, arrival, measured):
        pl, ol = self.sizes(uid)
        return Req(uid, arrival, pl, ol, measured)

    # ---------------------------------------------------------- tokens
    def tokens(self, uid, n):
        """The prompt of request ``uid``: ``n`` token ids from the seed."""
        rng = np.random.default_rng([self.seed, 0x70CE, uid])
        return rng.integers(0, self.vocab, n, dtype=np.int32)
