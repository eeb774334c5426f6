"""The one general traffic generator: a traffic file's parameters and a
seed in, requests or token rows out.

Every seed offers the same multiset of lengths and of arrival gaps in
another order, with other token ids: lengths and gaps are the quantiles of
their distribution at fixed, evenly spaced probabilities, shuffled by the
seed. So two seeds differ in what is in flight when, never in how much
work a run is offered.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics

import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")
_NORMAL = statistics.NormalDist()


def load(name: str, directory: str | None = None) -> dict:
    with open(os.path.join(directory or _DIR, f"{name}.json")) as fh:
        return json.load(fh)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of ``seed`` (any non-negative whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def stratified_lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers: the quantiles of a log-normal with median
    ``spec["median"]`` and shape ``spec["sigma"]`` at probabilities
    ``(i + 0.5) / n``, clipped to ``[spec["min"], spec["max"]]``. The same
    for every seed; ascending."""
    mu = math.log(float(spec["median"]))
    q = [math.exp(mu + float(spec["sigma"]) * _NORMAL.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), int(spec["min"]), int(spec["max"])).astype(
        np.int64)


def stratified_exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps in seconds: the quantiles of an exponential
    of mean ``1 / rate`` at probabilities ``(i + 0.5) / n``, rescaled so
    that they sum to exactly ``n / rate``. The same for every seed."""
    g = np.array([-math.log1p(-(i + 0.5) / n) for i in range(n)])
    return g * (n / rate) / g.sum()


def shuffled(values: np.ndarray, rng: np.random.Generator,
             strata=1) -> np.ndarray:
    """``values`` (ascending) in an order drawn from ``rng``. With
    ``strata`` > 1 the order is balanced: the values are cut into that many
    equal runs (quantile bands), and every ``strata`` consecutive places of
    the result hold one value of each band, in a drawn order. So any stretch
    of the sequence carries the whole distribution, and what a window sees
    depends on the seed far less than under a plain shuffle. A list such as
    ``[4, 32]`` balances at both scales: every 32 consecutive places hold
    one value of each of 32 bands, and every 4 consecutive places inside
    them one of each quarter of those 32."""
    levels = [int(s) for s in (strata if isinstance(strata, (list, tuple))
                               else [strata]) if int(s) > 1]
    n = len(values)
    if not levels:
        return values[rng.permutation(n)]
    outer = levels[-1]
    if n % outer:
        raise ValueError(f"{outer} strata do not divide {n} values")
    bands = [values[b * (n // outer):(b + 1) * (n // outer)]
             [rng.permutation(n // outer)] for b in range(outer)]
    blocks = np.stack(bands, axis=1)      # [n / outer, outer], rows ascending
    return np.concatenate([shuffled(row, rng, levels[:-1])
                           for row in blocks])


@dataclasses.dataclass
class Request:
    index: int                # position in the generated sequence
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    due_s: float | None       # open loop: seconds after the schedule's
                              # start at which it is due; None when closed


class RequestStream:
    """Requests without end. Each cycle of ``multiset`` requests holds the
    traffic file's multiset of prompt and output lengths, shuffled anew
    (prompts and outputs independently) by the seed and the cycle's number;
    open-loop arrival gaps are shuffled the same way. ``order_strata`` in
    the file balances the order (see :func:`shuffled`)."""

    def __init__(self, spec: dict, seed: int, vocab_size: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.n = int(spec["multiset"])
        self._prompts = stratified_lognormal(spec["prompt_tokens"], self.n)
        self._outputs = stratified_lognormal(spec["output_tokens"], self.n)
        rate = spec.get("arrival_rate_per_s")
        self._gaps = (stratified_exponential_gaps(float(rate), self.n)
                      if rate else None)
        self._cycle = -1
        self._buf: list[Request] = []
        self._next_index = 0
        self._clock = 0.0

    def _refill(self) -> None:
        self._cycle += 1
        rng = rng_for(self.seed, 1000 + self._cycle)
        strata = self.spec.get("order_strata", 1)
        p = shuffled(self._prompts, rng, strata)
        o = shuffled(self._outputs, rng, strata)
        g = (shuffled(self._gaps, rng, strata)
             if self._gaps is not None else None)
        for i in range(self.n):
            due = None
            if g is not None:
                self._clock += float(g[i])
                due = self._clock
            self._buf.append(Request(
                index=self._next_index,
                prompt=rng.integers(0, self.vocab, size=int(p[i]),
                                    dtype=np.int64).astype(np.int32),
                max_new_tokens=int(o[i]), due_s=due))
            self._next_index += 1

    def peek(self) -> Request:
        if not self._buf:
            self._refill()
        return self._buf[0]

    def pop(self) -> Request:
        r = self.peek()
        self._buf.pop(0)
        return r


def token_rows(spec: dict, seed: int, vocab_size: int) -> np.ndarray:
    """Training rows ``[rows, seq_len + 1]`` of int32 ids, uniform over the
    vocabulary, so that all rows differ."""
    rng = rng_for(seed, 7)
    return rng.integers(0, int(vocab_size),
                        size=(int(spec["rows"]), int(spec["seq_len"]) + 1),
                        dtype=np.int64).astype(np.int32)
