"""The one traffic generator: reads a mix's parameters (a JSON file under
bench/traffic/) and draws a seeded open-loop request schedule.

Every seed gets the same multiset of sizes, tenants and inter-arrival gaps:
each quantity is drawn at the stratified quantiles (i + 0.5) / n of its
distribution and then permuted, by the seed or, where the mix names an
`order_seed`, by that fixed seed, so that every run offers the same
schedule and the seed draws only the token ids and the sampling seeds. So
two seeds offer the same work, and a run's spread is the system's, not
the generator's. Prompt token ids are uniform over the vocabulary.

Mix parameters (all lengths in tokens):
  arrival.process      "poisson" (exponential gaps at the cell's rate)
  arrival.backlog      requests already waiting when the traffic starts
                       (an overloaded service's queue), optional
  prompt.tenants       shared system prompts; 0 = every prompt unique
  prompt.system_tokens length of each tenant's system prompt
  prompt.tenant_zipf_s popularity exponent over tenants (rank k ~ 1/k^s)
  prompt.suffix        {"median", "sigma", "min", "max"}: lognormal length
                       of the part after the system prompt (or the whole
                       prompt when tenants == 0)
  output               {"median", "sigma", "min", "max"}: lognormal
  sampling.greedy_share   share of requests decoded greedily
  sampling.temperature / top_p / top_k   the others' sampling
  lead_in_s            seconds of traffic before the window opens
  order_seed           fixes the order of the schedule for every seed,
                       optional
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_STD_NORMAL = NormalDist()


@dataclass(frozen=True)
class Sampling:
    temperature: float
    top_p: float
    top_k: int
    seed: int


@dataclass(frozen=True)
class RequestSpec:
    rid: int
    offset_s: float  # due time, seconds after the traffic starts
    prompt: np.ndarray  # int32 token ids
    gen_len: int
    sampling: Optional[Sampling]  # None: greedy
    tenant: int  # -1: no shared prefix


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n lengths at the stratified quantiles of a lognormal (median,
    sigma) clipped to [min, max], in a seeded order."""
    mu = math.log(spec["median"])
    z = np.array([_STD_NORMAL.inv_cdf(u) for u in _strata(n)])
    vals = np.clip(np.rint(np.exp(mu + spec["sigma"] * z)),
                   spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def exponential_gaps(rate_rps: float, n: int, rng) -> np.ndarray:
    """n inter-arrival gaps at the stratified quantiles of Exp(rate)."""
    return rng.permutation(-np.log1p(-_strata(n)) / rate_rps)


def zipf_counts(n_tenants: int, s: float, n: int) -> np.ndarray:
    """How many of n requests go to each tenant under Zipf(s) popularity,
    by largest remainder so the counts sum to n."""
    w = 1.0 / np.arange(1, n_tenants + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def n_requests(rate_rps: float, horizon_s: float) -> int:
    return max(int(math.ceil(rate_rps * horizon_s)), 1)


def generate(mix: dict, *, seed: int, rate_rps: float, horizon_s: float,
             vocab_size: int) -> List[RequestSpec]:
    """The request schedule for one run: due times cover [0, horizon_s]
    on average. Deterministic in (mix, seed, rate_rps, horizon_s)."""
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']}")
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    order = (np.random.default_rng(np.random.SeedSequence(
        int(mix["order_seed"]))) if "order_seed" in mix else rng)
    backlog = int(mix["arrival"].get("backlog", 0))
    n = backlog + n_requests(rate_rps, horizon_s)
    offsets = np.concatenate([
        np.zeros(backlog),
        np.cumsum(exponential_gaps(rate_rps, n - backlog, order))])
    p = mix["prompt"]
    tenants = int(p.get("tenants", 0))
    if tenants:
        sys_tok = int(p["system_tokens"])
        system = rng.integers(0, vocab_size, size=(tenants, sys_tok),
                              dtype=np.int32)
        tenant_of = order.permutation(np.repeat(
            np.arange(tenants), zipf_counts(tenants, p["tenant_zipf_s"], n)))
    else:
        tenant_of = np.full(n, -1)
    suffix = lognormal_lengths(p["suffix"], n, order)
    gen = lognormal_lengths(mix["output"], n, order)
    s = mix["sampling"]
    n_greedy = int(round(n * s["greedy_share"]))
    greedy = order.permutation(np.arange(n) < n_greedy)
    req_seeds = rng.integers(0, 2**31 - 1, size=n)
    out = []
    for i in range(n):
        body = rng.integers(0, vocab_size, size=(int(suffix[i]),),
                            dtype=np.int32)
        t = int(tenant_of[i])
        prompt = np.concatenate([system[t], body]) if t >= 0 else body
        samp = None if greedy[i] else Sampling(
            float(s["temperature"]), float(s["top_p"]), int(s["top_k"]),
            int(req_seeds[i]))
        out.append(RequestSpec(rid=i, offset_s=float(offsets[i]),
                               prompt=prompt, gen_len=int(gen[i]),
                               sampling=samp, tenant=t))
    return out

