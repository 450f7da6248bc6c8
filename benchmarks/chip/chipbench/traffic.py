"""The one traffic generator: a mix's parameters -> seeded requests.

A mix file (``traffic/<mix>.json``) gives the lengths, the loop and the
batch cap; nothing here knows any mix by name.

Lengths.  Prompt and output lengths follow lognormal laws clipped to
``[lo, hi]``.  Every seed gets the same lengths in another order: the
lengths of each block of ``block`` consecutive requests are the block's
quantiles of the law, shuffled by the seed (prompt and output lengths
separately).  A run's work then does not depend on its seed, so runs of
different seeds spread no wider than two runs of one seed.  The seed
also draws every prompt token.

Loops.  ``closed``: ``clients`` callers that each send their next request
when the last one has finished (batch callers that wait for a reply).
``open``: requests due on a schedule whatever the system does,
``arrivals.process`` = ``bursty`` (clustered Poisson, below) or
``poisson``, at ``arrivals.rate`` requests per second.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Mix:
    name: str
    loop: str                  # closed | open
    clients: int               # closed loop: callers in flight
    max_batch: int             # the serving loop's composed batch cap
    prompt: dict               # median, sigma, lo, hi
    output: dict
    block: int
    requests: int              # requests generated per run
    cache_window: int          # the one KV window every request gets
    check: dict                # sample of the served requests to judge
    arrivals: Optional[dict] = None

    @classmethod
    def from_dict(cls, raw: dict) -> "Mix":
        mix = cls(name=raw["name"], loop=raw["loop"],
                  clients=int(raw.get("clients", 0)),
                  max_batch=int(raw["max_batch"]), prompt=raw["prompt"],
                  output=raw["output"], block=int(raw["block"]),
                  requests=int(raw["requests"]),
                  cache_window=int(raw["cache_window"]),
                  check=raw["check"], arrivals=raw.get("arrivals"))
        if mix.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {mix.loop!r}")
        if mix.loop == "closed" and mix.clients < 1:
            raise ValueError("a closed loop needs clients >= 1")
        if mix.loop == "open" and not mix.arrivals:
            raise ValueError("an open loop needs arrivals")
        if mix.prompt["hi"] + mix.output["hi"] + 1 > mix.cache_window:
            raise ValueError("cache_window cannot hold the longest request")
        return mix


@dataclass
class GenRequest:
    index: int
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int
    due_s: Optional[float]     # open loop: seconds after the window opens


def quantile_lengths(law: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a lognormal law of the given median
    and sigma, rounded and clipped to ``[lo, hi]``."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = np.exp(math.log(law["median"]) + law["sigma"] * np.asarray(z))
    return np.clip(np.rint(vals), law["lo"], law["hi"]).astype(np.int64)


def poisson_arrivals(rate: float, n: int, seed: int = 0) -> List[float]:
    """Arrival times of ``n`` requests from a Poisson process with
    ``rate`` req/s; ``rate <= 0`` means everything arrives at t=0.
    (Copied from ``repro.core.timing``.)"""
    if rate <= 0:
        return [0.0] * n
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n)).tolist()


def bursty_arrivals(rate: float, n: int, seed: int = 0, *,
                    burst_size: float = 4.0,
                    spread_frac: float = 0.1) -> List[float]:
    """``n`` arrival times whose long-run rate is ``rate`` req/s but
    which land in tight clusters: cluster starts are the plain Poisson
    process at ``rate / burst_size``, each cluster carries a geometric
    number of requests (mean ``burst_size``), and members within a
    cluster spread by exponential jitter with mean ``spread_frac /
    rate``.  (Copied from ``repro.serve.workload``.)"""
    if rate <= 0 or n <= 0:
        return [0.0] * max(n, 0)
    if burst_size < 1:
        raise ValueError("burst_size must be >= 1")
    rng = np.random.default_rng(seed)
    starts = poisson_arrivals(rate / burst_size, n, seed=seed + 1)
    out: List[float] = []
    for t0 in starts:
        k = int(rng.geometric(1.0 / burst_size))
        jitter = np.cumsum(rng.exponential(spread_frac / rate, size=k))
        out.extend(float(t0 + j) for j in jitter)
        if len(out) >= n:
            break
    return sorted(out)[:n]


ARRIVALS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals}


def make_requests(mix: Mix, vocab_size: int, seed: int) -> List[GenRequest]:
    rng = np.random.default_rng(seed)
    p_block = quantile_lengths(mix.prompt, mix.block)
    o_block = quantile_lengths(mix.output, mix.block)
    n_blocks = -(-mix.requests // mix.block)
    prompts = np.concatenate([rng.permutation(p_block)
                              for _ in range(n_blocks)])[:mix.requests]
    outputs = np.concatenate([rng.permutation(o_block)
                              for _ in range(n_blocks)])[:mix.requests]
    due: List[Optional[float]] = [None] * mix.requests
    if mix.loop == "open":
        arr = dict(mix.arrivals)
        process = ARRIVALS[arr.pop("process")]
        rate = float(arr.pop("rate"))
        due = process(rate, mix.requests, seed=int(rng.integers(2**31)),
                      **arr)
    return [GenRequest(index=i,
                       prompt=rng.integers(0, vocab_size, int(p)).astype(
                           np.int32),
                       max_new_tokens=int(o), due_s=due[i])
            for i, (p, o) in enumerate(zip(prompts, outputs))]


def prompt_lengths(mix: Mix) -> List[int]:
    """Every prompt length the mix sends (the same set for every seed)."""
    return sorted(set(int(n) for n in quantile_lengths(mix.prompt,
                                                       mix.block)))
