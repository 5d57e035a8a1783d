"""One general traffic generator; a mix is a data file of parameters.

Every seed gets the SAME sizes and arrival gaps in the same order: a
``population`` of requests whose lengths are the stratified quantiles
of the mix's distributions, permuted block after block by the mix's own
``order_seed``.  Token values (and the weights) come from ``--seed``.
So runs with different seeds offer the same work, and a run's spread
is the system's, not the draw's.

A mix file (``traffic/<mix>.json``) has:

``loop``        ``"open"`` (arrivals on a schedule, ``rate_per_s``) or
                ``"closed"`` (``clients`` callers, each sending its
                next request when the last completes)
``slots``, ``max_seq``   how the server is sized for this mix
``ramp_s``      load offered before the window opens (set-up)
``drain_s``     how long after the window its requests may still finish
``population``  size of the permuted multiset
``prompt``, ``output``   length distributions (``lognormal`` by median
                and sigma, or ``uniform``), clipped to ``min``/``max``
                and rounded to ``quantum``
``sharing``     optional: ``documents`` (a length distribution), each
                asked ``askings`` times, ``lag`` documents apart, with
                the ``prompt`` as the question appended
``warm``        the warm-up scenes (see ``run.py``)
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


def spread(dist: dict, count: int):
    """``count`` stratified quantiles of a length distribution."""
    if dist["dist"] == "mixture":
        # Parts in proportion to their weights, each its own spread.
        weights = np.asarray([part["weight"] for part in dist["parts"]],
                             float)
        shares = np.floor(np.cumsum(weights / weights.sum()) * count
                          + 0.5).astype(int)
        counts = np.diff(np.concatenate([[0], shares]))
        return np.concatenate([spread(part, n) for part, n
                               in zip(dist["parts"], counts) if n])
    probs = (np.arange(count) + 0.5) / count
    if dist["dist"] == "lognormal":
        normal = statistics.NormalDist()
        values = [dist["median"] * np.exp(dist["sigma"] * normal.inv_cdf(p))
                  for p in probs]
    elif dist["dist"] == "uniform":
        values = [dist["min"] + (dist["max"] - dist["min"]) * p
                  for p in probs]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    quantum = dist.get("quantum", 1)
    values = np.clip(np.asarray(values), dist["min"], dist["max"])
    return (np.round(values / quantum).astype(int) * quantum).clip(
        quantum, None)


def gaps(rate_per_s: float, count: int):
    """``count`` stratified exponential gaps whose mean is exactly
    ``1 / rate_per_s``."""
    probs = (np.arange(count) + 0.5) / count
    raw = -np.log1p(-probs)
    return raw / raw.mean() / rate_per_s


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray
    max_new: int
    shared: int = 0            # leading tokens shared with a document
    due: float = 0.0           # open loop: seconds after load starts


class Mix:
    """The endless request stream of one mix under one seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int,
                 seconds: float = 0.0):
        self.traffic = traffic
        self.vocab = vocab
        # The ORDER of sizes and gaps belongs to the mix (its
        # ``order_seed``), the token VALUES to --seed: under every seed
        # the same requests arrive at the same times with other
        # contents (and other weights).  Measured before this rule
        # (PR 23, 32 callers, closed loop): two runs of one seed agreed
        # to 0.01 %, six seeds spread 6-11 %, because which of a
        # heavy-tailed population lands in a 40 s window is the order.
        self.order = np.random.default_rng(
            [int(traffic.get("order_seed", 0)), 23])
        self.rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 23])
        self.count = traffic["population"]
        self.ramp_count = 0
        if traffic["loop"] == "open" and seconds:
            # One block of the population IS one window: the requests
            # due in the window are the same multiset under every seed
            # (the ramp before it is a block of its own).
            rate = traffic["rate_per_s"]
            self.count = max(1, round(rate * seconds))
            self.ramp_count = round(rate * traffic["ramp_s"])
        self.prompts = spread(traffic["prompt"], self.count)
        self.outputs = spread(traffic["output"], self.count)
        sharing = traffic.get("sharing")
        self.documents = (spread(sharing["documents"], self.count)
                          if sharing else None)
        self.gaps = None
        if traffic["loop"] == "open":
            self.gaps = gaps(traffic["rate_per_s"], self.count)
            if seconds:
                self.gaps *= seconds / self.gaps.sum()

    def _tokens(self, n: int):
        return self.rng.integers(1, self.vocab, int(n)).astype(np.int32)

    def _shared_stream(self):
        """Documents asked ``askings`` times, ``lag`` documents apart:
        step t sends asking k of document t - k * lag."""
        sharing = self.traffic["sharing"]
        askings, lag = sharing["askings"], sharing["lag"]
        live = {}
        step = 0
        while True:
            order_d = self.order.permutation(self.count)
            order_p = self.order.permutation(self.count * askings)
            order_o = self.order.permutation(self.count * askings)
            sent = 0
            for d in order_d:
                live[step] = self._tokens(self.documents[d])
                for k in range(askings):
                    document = live.get(step - k * lag)
                    if document is None:
                        continue
                    p = order_p[sent % len(order_p)] % self.count
                    o = order_o[sent % len(order_o)] % self.count
                    sent += 1
                    question = self._tokens(self.prompts[p])
                    yield (np.concatenate([document, question]),
                           int(self.outputs[o]), len(document))
                live.pop(step - (askings - 1) * lag, None)
                step += 1

    def requests(self):
        """Yields :class:`Request` for ever (closed loop takes what it
        needs; open loop stops at its horizon)."""
        index = 0
        # A block ends exactly on a window's edge; the hair's breadth
        # puts its last request inside the window it belongs to.
        clock = -1e-6
        gap_iter = self._gap_stream() if self.gaps is not None else None
        source = (self._shared_stream() if self.documents is not None
                  else self._plain_stream())
        for prompt, max_new, shared in source:
            if gap_iter is not None:
                clock += next(gap_iter)
            yield Request(index, prompt, max_new, shared, clock)
            index += 1

    def _plain_stream(self):
        if self.ramp_count:
            traffic, n = self.traffic, self.ramp_count
            sizes = zip(self.order.permutation(spread(traffic["prompt"], n)),
                        self.order.permutation(spread(traffic["output"], n)))
            for prompt, output in sizes:
                yield self._tokens(prompt), int(output), 0
        while True:
            order_p = self.order.permutation(self.count)
            order_o = self.order.permutation(self.count)
            for p, o in zip(order_p, order_o):
                yield (self._tokens(self.prompts[p]),
                       int(self.outputs[o]), 0)

    def _gap_stream(self):
        if self.ramp_count:
            ramp = gaps(1.0, self.ramp_count)
            yield from self.order.permutation(
                ramp * self.traffic["ramp_s"] / ramp.sum())
        while True:
            yield from self.gaps[self.order.permutation(self.count)]
