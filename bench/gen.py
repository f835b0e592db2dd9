"""One traffic generator for every mix in ``bench/traffic/``.

A mix file holds parameters only: the arrival process, the prompt and
output length distributions and prompt sharing. The cell fixes the rate.
Arrivals and lengths follow ``serving/load.py`` (exponential gaps for a
Poisson process, bursts of simultaneous arrivals, shared system prompts),
with one change: the gaps and lengths are stratified quantiles of each
distribution, in one fixed order that is balanced in time (each run of
``BLOCK`` consecutive draws takes one value from each of ``BLOCK``
strata, shortest quarter to longest). Every seed gets the same sizes and
arrivals in the same order; the seed draws the prompts' tokens (and the
weights). With requests that live tens of seconds in a 51 s window, a
seed that reordered the sizes changed how much work fell inside the
window: output tokens/s spread 5-10% and TTFT p95 40% across seeds while
one seed repeated within 0.2-3% (measured on a TPU v5e; PERF.md).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    due: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def rng_for(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  salt])


BLOCK = 4


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def balanced(values: np.ndarray, rng) -> np.ndarray:
    """``values`` (sorted) in a seeded order in which every ``BLOCK``
    consecutive entries hold one from each stratum of the sorted list."""
    n = len(values)
    strata = [rng.permutation(values[j * n // BLOCK:(j + 1) * n // BLOCK])
              for j in range(BLOCK)]
    out = []
    for k in range(max(len(st) for st in strata)):
        out.extend(rng.permutation([st[k] for st in strata if k < len(st)]))
    return np.asarray(out)


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths with the distribution's stratified quantiles, in a
    balanced seeded order. ``spec``: {"dist": "loguniform"|"uniform"|"fixed",
    "lo", "hi"} or {"dist": "fixed", "value"}."""
    q = quantiles(n)
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, spec["value"], np.float64)
    elif dist == "uniform":
        out = spec["lo"] + q * (spec["hi"] - spec["lo"])
    elif dist == "loguniform":
        out = spec["lo"] * (spec["hi"] / spec["lo"]) ** q
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return balanced(np.rint(out).astype(np.int64), rng)


def arrivals(spec: dict, rate: float, n: int, rng) -> np.ndarray:
    """Due times of ``n`` requests at a mean ``rate`` req/s.

    ``poisson``: exponential inter-arrival gaps (stratified quantiles in
    a balanced seeded order). ``burst``: groups of ``burst`` simultaneous arrivals,
    the groups' gaps exponential at ``rate / burst``."""
    kind = spec["arrivals"]
    if rate <= 0:
        raise ValueError("rate must be > 0")
    if kind == "poisson":
        gaps = -np.log1p(-quantiles(n)) / rate
        return np.cumsum(balanced(gaps, rng))
    if kind == "burst":
        b = int(spec["burst"])
        groups = -(-n // b)
        gaps = -np.log1p(-quantiles(groups)) / (rate / b)
        starts = np.cumsum(balanced(gaps, rng))
        return np.repeat(starts, b)[:n]
    raise ValueError(f"unknown arrival process {kind!r}")


ORDER_SEED = 0  # the one order of sizes and arrivals every seed shares


def requests(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Req]:
    """The open-loop schedule of one run: ``rate * seconds`` requests."""
    n = max(1, int(round(rate * seconds)))
    due = arrivals(mix, rate, n, rng_for(ORDER_SEED, 1))
    plen = lengths(mix["prompt_len"], n, rng_for(ORDER_SEED, 2))
    olen = lengths(mix["output_len"], n, rng_for(ORDER_SEED, 3))
    tok = rng_for(seed, 4)
    share = mix.get("shared_prefix")
    systems = []
    if share:
        systems = [tok.integers(0, vocab, share["len"], dtype=np.int32)
                   for _ in range(share["n_system"])]
    pick = rng_for(seed, 5)
    out = []
    for i in range(n):
        body = tok.integers(0, vocab, int(plen[i]), dtype=np.int32)
        if systems and pick.random() < share["p"]:
            body = np.concatenate(
                [systems[int(pick.integers(len(systems)))], body])
        out.append(Req(float(due[i]), body, int(olen[i])))
    return out
