"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and makes its requests from a seed.

A mix is open loop (arrivals at a fixed rate, Poisson gaps) or closed
loop (a fixed number of requests kept in the system).  Sizes are
lognormal, given by median and sigma and clipped.  An optional
``prefix`` block makes every prompt one of a few shared documents
followed by an unshared question.

Every seed gets the same work on the same schedule.  The n requests of
a batch (the warm-up's, the window's, each block of ``strata`` in a
closed loop) take the sizes at the quantiles ``(i + 0.5) / n``; the
gaps of an open loop are the exponential quantiles of the batch's
request count.  Both are shuffled once, by a stream that is the mix's
and not the seed's: a window holds only a few long requests, so a
seed that reordered them would change the work the window sees.  The
seed makes the token ids and the documents (and the weights), so runs
of one seed repeat exactly and runs of different seeds differ in
content, not in amount or timing.  (The arrival arithmetic is restated
in seconds from ``sim/traffic.py``'s engine-step version.)
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

_NORMAL = NormalDist()
SCHEDULE = 20191118  # the schedule's stream, fixed for every seed


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it: due time in seconds from
    the window's start (negative in the warm-up), prompt, output cap."""
    due: float
    prompt: np.ndarray
    max_new: int
    doc: int = -1


def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


class Traffic:
    def __init__(self, spec: Dict, vocab: int, seed: int, seconds: float):
        self.spec = spec
        self.vocab = vocab
        self.seconds = float(seconds)
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop {self.loop!r}: open or closed")
        self.strata = int(spec.get("strata", 32))
        self._seed = int(seed)
        pre = spec.get("prefix")
        self.docs: List[np.ndarray] = []
        if pre:
            rng = self._rng("docs")
            self.docs = [rng.integers(0, vocab, pre["doc_len"]).astype(np.int32)
                         for _ in range(pre["docs"])]
        self._closed = self.closed_stream()

    def _rng(self, name: str) -> np.random.Generator:
        """An independent stream of the seed per purpose."""
        return np.random.default_rng([self._seed, zlib.crc32(name.encode())])

    @staticmethod
    def _order(name: str) -> np.random.Generator:
        """The schedule's stream per purpose: the same for every seed."""
        return np.random.default_rng([SCHEDULE, zlib.crc32(name.encode())])

    def _make(self, prompt_len: int, max_new: int, rng, doc: int
              ) -> Planned:
        body = rng.integers(0, self.vocab, prompt_len).astype(np.int32)
        if doc >= 0:
            body = np.concatenate([self.docs[doc], body])
        return Planned(0.0, body, int(max_new), doc)

    def _batch(self, n: int, name: str) -> List[Planned]:
        """n requests whose sizes are the n-quantiles of the mix, in the
        schedule's order; documents taken in turn, in the schedule's
        order; token ids from the seed."""
        order = self._order(name)
        plens = order.permutation(_lognormal_quantiles(self.spec["prompt"], n))
        outs = order.permutation(_lognormal_quantiles(self.spec["output"], n))
        docs = [-1] * n
        if self.docs:
            nd = len(self.docs)
            docs = np.concatenate([order.permutation(nd)
                                   for _ in range(-(-n // nd))])[:n]
        rng = self._rng(name)
        return [self._make(int(a), int(b), rng, int(d))
                for a, b, d in zip(plens, outs, docs)]

    def closed_stream(self) -> Iterator[Planned]:
        """The closed loop's endless stream, in stratified blocks."""
        block = 0
        while True:
            yield from self._batch(self.strata, f"block{block}")
            block += 1

    def primer(self) -> List[Planned]:
        """Set-up requests that fill what the traffic keeps warm: one per
        shared document, so the prefix cache holds every document before
        arrivals start."""
        rng = self._rng("primer")
        return [Planned(0.0, np.concatenate([d, rng.integers(
            0, self.vocab, 16).astype(np.int32)]), 1, i)
            for i, d in enumerate(self.docs)]

    def _arrivals(self, span: float, name: str) -> np.ndarray:
        """Open-loop arrival offsets in [0, span): the exponential
        quantiles of ``rate * span`` gaps in the schedule's order."""
        rate = float(self.spec["rate_per_s"])
        n = max(1, int(round(rate * span)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        gaps = self._order(name).permutation(gaps)
        t = np.cumsum(gaps) - gaps[0]
        return t[t < span]

    def warm_plan(self) -> List[Planned]:
        """Requests due in the warm-up, at negative offsets (open loop);
        the closed loop fills its clients at once."""
        warm = float(self.spec.get("warm_s", 0.0))
        if self.loop == "closed":
            return [self.next_closed() for _ in range(self.concurrency)]
        times = self._arrivals(warm, "warmgap") if warm > 0 else []
        out = self._batch(len(times), "warm")
        for r, t in zip(out, times):
            r.due = float(t) - warm
        return out

    def window_plan(self) -> List[Planned]:
        """Requests due in the window (open loop only)."""
        if self.loop == "closed":
            return []
        times = self._arrivals(self.seconds, "gaps")
        out = self._batch(len(times), "window")
        for r, t in zip(out, times):
            r.due = float(t)
        return out

    def next_closed(self) -> Planned:
        return next(self._closed)

    @property
    def concurrency(self) -> Optional[int]:
        return int(self.spec["concurrency"]) if self.loop == "closed" \
            else None

    @property
    def warm_s(self) -> float:
        return float(self.spec.get("warm_s", 0.0))
