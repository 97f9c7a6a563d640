"""The one traffic generator: a mix is a JSON file of parameters under
`bench/traffic/`, read here.

Every seed gets the same schedule: the same prompt and output lengths, in
the same order, at the same arrival times (all from fixed streams); the
seed draws every token of every prompt. So runs of different seeds do the
same work on different contents. (With the order drawn from the seed too,
chat's first-token tails on one TPU v5e spread 12-24% between seeds
against 3-7% between two runs of one seed: the order set them.)

Mix keys:
  loop            "closed" (sessions built in set-up, then decoded) or
                  "open" (requests submitted at their due times)
  serve           batch_slots, max_len, prefill_chunk, page_size
  requests        closed: the number of sessions
  rate_rps        open: mean arrival rate, requests per second
  burst           open, optional: {"period_s", "high_s", "high", "low"}: the
                  rate is high x rate_rps for the first high_s seconds of
                  every period_s and low x rate_rps for the rest
  prompt_tokens   {"dist": "uniform", "lo", "hi"} or
  output_tokens   {"dist": "lognormal", "median", "sigma", "lo", "hi"} or
                  {"dist": "fixed", "value"}
  warmup_requests open: short requests served to completion before the window
  check           sample_requests, sample_tokens, mean_gap_sd (the limit)
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

# arrival times and the order of lengths come from fixed streams
_ARRIVAL_STREAM = 0x5EED
_ORDER_STREAM = 0x0DE7


@dataclasses.dataclass
class RequestRecord:
    index: int
    due: float                     # seconds after the window opens
    prompt: np.ndarray
    max_new: int
    in_window: bool = True
    submitted: float | None = None  # benchmark clock, seconds
    request_id: int = -1
    failed: bool = False
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)


def _quantiles(spec: dict, n: int) -> list[int]:
    """n lengths at the mid-quantiles (i + 0.5) / n of the distribution."""
    kind = spec["dist"]
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        return [int(spec["value"])] * n
    if kind == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        return [int(round(lo + u * (hi - lo))) for u in us]
    if kind == "lognormal":
        nd = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
        return [int(min(spec["hi"], max(spec["lo"], round(math.exp(nd.inv_cdf(u))))))
                for u in us]
    raise ValueError(f"unknown length distribution {kind!r}")


def rate_at(mix: dict, t: float) -> float:
    rate = float(mix["rate_rps"])
    burst = mix.get("burst")
    if not burst:
        return rate
    phase = t % burst["period_s"]
    return rate * (burst["high"] if phase < burst["high_s"] else burst["low"])


def arrivals(mix: dict, seconds: float) -> list[float]:
    """Due times in [0, seconds) of an inhomogeneous Poisson process, drawn
    by thinning from the fixed arrival stream."""
    rng = np.random.default_rng(_ARRIVAL_STREAM)
    burst = mix.get("burst")
    peak = float(mix["rate_rps"]) * (max(burst["high"], burst["low"]) if burst else 1.0)
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / peak)
        if t >= seconds:
            return out
        if rng.random() * peak < rate_at(mix, t):
            out.append(t)


def make_requests(mix: dict, *, seed: int, seconds: float,
                  vocab: int) -> tuple[list[RequestRecord], list[RequestRecord]]:
    """(window requests, warm-up requests) for one run."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "closed":
        n = int(mix["requests"])
        dues = [0.0] * n
    else:
        dues = arrivals(mix, seconds)
        n = len(dues)
    prompts = _quantiles(mix["prompt_tokens"], n)
    outputs = _quantiles(mix["output_tokens"], n)
    fixed = np.random.default_rng(_ORDER_STREAM)
    order_p = fixed.permutation(n)
    order_o = fixed.permutation(n)
    reqs = []
    for i in range(n):
        plen = prompts[order_p[i]]
        reqs.append(RequestRecord(
            index=i, due=dues[i], max_new=outputs[order_o[i]],
            prompt=rng.integers(0, vocab, plen, dtype=np.int32)))
    warm = []
    for i in range(int(mix.get("warmup_requests", 0))):
        plen = int(mix["prompt_tokens"].get("lo", 32))
        warm.append(RequestRecord(index=-1 - i, due=0.0, max_new=4,
                                  in_window=False,
                                  prompt=rng.integers(0, vocab, plen,
                                                      dtype=np.int32)))
    return reqs, warm
