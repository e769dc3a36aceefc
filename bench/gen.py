"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
and a seed into the requests of a run.

Every seed gets the same multiset of sizes and the same arrival times; the
seed only orders the sizes and draws the token ids. Sizes are
block-stratified: each consecutive block of ``block`` requests holds the
distribution's quantiles at (i + 0.5) / block in a fresh order, so any
stretch of the stream a window reaches has nearly the same mix whatever the
seed.

Mix keys (a mix uses the ones its ``loop`` needs):

``kind``          "lm" (token prompts)
``loop``          "closed" (a queue kept full), or "open" (arrivals on a
                  schedule, whatever the system does)
``queue_depth``   closed loop: requests kept queued
``requests``      closed loop: requests generated
``block``         stratification block
``prompt_len`` / ``output_len``
                  {"median", "sigma", "min", "max"}: lognormal, clipped
``shared_prefix`` {"count", "len", "zipf"}: prompts start with one of
                  ``count`` shared prefixes of ``len`` tokens, the k-th
                  (from 1) chosen with weight k**-zipf
``rate``          open loop: base arrivals per second
``burst``         {"every_s", "length_s", "factor"}: the rate is
                  ``factor`` x ``rate`` during ``length_s`` seconds at the
                  start of every ``every_s`` seconds of the window
``arrival_seed``  open loop: the seed of the one Poisson draw of arrival
                  times that every run meets
``preroll_s``     open loop: arrivals before the window starts
``tail_s``        open loop: longest wait after the window for requests
                  due in it
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np
from scipy import stats as sps


@dataclasses.dataclass
class Item:
    """One generated LM request: ``prompt`` (int32 ids), ``out_len`` tokens
    to generate, ``due_s`` relative to the window start (open loop) and the
    shared ``prefix`` it starts with (-1: none)."""
    uid: int
    prompt: np.ndarray
    out_len: int
    due_s: Optional[float] = None
    prefix: int = -1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose; any size of seed."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a clipped lognormal, as ints."""
    p = (np.arange(n) + 0.5) / n
    x = spec["median"] * np.exp(spec["sigma"] * sps.norm.ppf(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def stratified(spec: dict, n: int, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` sizes: each block of ``block`` is the quantile set, shuffled."""
    q = lognormal_quantiles(spec, block)
    out = [rng.permutation(q) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def zipf_choices(count: int, s: float, n: int, block: int,
                 rng: np.random.Generator) -> np.ndarray:
    """Prefix index per request: per block, the Zipf(s) shares of
    ``count`` prefixes rounded to whole counts (largest remainder), shuffled."""
    w = np.arange(1, count + 1, dtype=np.float64) ** -s
    share = w / w.sum() * block
    k = np.floor(share).astype(int)
    k[np.argsort(-(share - k))[:block - k.sum()]] += 1
    base = np.repeat(np.arange(count), k)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """Due times (s, window start = 0) over [-preroll_s, seconds + tail_s).

    One draw of a Poisson process whose rate is ``rate``, and ``factor`` x
    ``rate`` in the bursts: the points of a unit-rate Poisson process
    (exponential gaps) mapped through the inverse of the cumulative rate.
    The draw comes from the mix's ``arrival_seed``, not the run's seed, so
    every run meets the same arrivals and the run's seed only orders the
    requests that fill them; a higher ``rate`` packs the same points
    closer."""
    lo, hi = -mix.get("preroll_s", 0.0), seconds + mix.get("tail_s", 0.0)
    rate = mix["rate"]
    b = mix.get("burst")
    edges = [(lo, hi, rate)]
    if b:
        edges = []
        k = math.floor(lo / b["every_s"])
        t = lo
        while t < hi:
            start = k * b["every_s"]
            burst_end = start + b["length_s"]
            nxt = start + b["every_s"]
            if t < burst_end:
                edges.append((t, min(burst_end, hi), rate * b["factor"]))
                t = min(burst_end, hi)
            if t < hi:
                edges.append((t, min(nxt, hi), rate))
                t = min(nxt, hi)
            k += 1
    knots_t = np.array([lo] + [s1 for _, s1, _ in edges])
    knots_m = np.concatenate([[0.0], np.cumsum(
        [r * (s1 - s0) for s0, s1, r in edges])])
    rng = rng_for(mix.get("arrival_seed", 0), 1)
    points, total = [], 0.0
    while total < knots_m[-1]:
        gaps = rng.exponential(1.0, 256)
        points.append(total + np.cumsum(gaps))
        total = points[-1][-1]
    m = np.concatenate(points) if points else np.zeros(0)
    return np.interp(m[m < knots_m[-1]], knots_m, knots_t)


def lm_items(mix: dict, seed: int, vocab: int, seconds: float
             ) -> List[Item]:
    """The LM requests of one run, in submission order."""
    rng = rng_for(seed, 0)
    if mix["loop"] == "open":
        due = arrival_times(mix, seconds)
        n = len(due)
    else:
        due, n = None, mix["requests"]
    block = mix.get("block", 64)
    plens = stratified(mix["prompt_len"], n, block, rng_for(seed, 2))
    outs = stratified(mix["output_len"], n, block, rng_for(seed, 3))
    sp = mix.get("shared_prefix")
    prefixes, which = [], np.full(n, -1)
    if sp:
        prefixes = [rng.integers(0, vocab, sp["len"], dtype=np.int32)
                    for _ in range(sp["count"])]
        which = zipf_choices(sp["count"], sp["zipf"], n, block,
                             rng_for(seed, 4))
    items = []
    for i in range(n):
        body = rng.integers(0, vocab, int(plens[i]), dtype=np.int32)
        k = int(which[i])
        prompt = body if k < 0 else np.concatenate([prefixes[k], body])
        items.append(Item(uid=i, prompt=prompt, out_len=int(outs[i]),
                          due_s=None if due is None else float(due[i]),
                          prefix=k))
    return items

