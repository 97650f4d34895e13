"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests with due times.

Every seed offers the same work in the measured window, in another order.
Poisson traffic is made in three stretches: a ramp of ``ramp_s`` before the
window, the window itself, and a tail of ``tail_s`` past its close, so the
window sees a filled system and every request due in it finds the load that
follows it. Each stretch gets ``rate x length`` requests whose prompt
lengths, output lengths and inter-arrival gaps are the distributions'
quantiles at ``(i + 0.5) / n``; the gaps are scaled to fill the stretch
exactly. A backlog is one stretch of ``requests``, due at 0 in full.

The seed orders each of those lists and draws the token ids. The order is
stratified: the sorted values are split into ``GROUP`` equal strata, and
every run of ``GROUP`` consecutive requests takes one value from each, so
any stretch of the stream holds a like mix of short and long requests and
of short and long gaps. Without it, which long requests a seed put near the
window's edges moved tokens per second by some 7% from seed to seed.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Optional, Tuple

import numpy as np

GROUP = 10


@dataclasses.dataclass
class Req:
    id: int
    due: float
    prompt: np.ndarray          # int32 token ids
    max_new: int
    system: int = -1            # shared system prompt index, -1 for none


def _quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified integer sizes of a ``lognormal`` (median, sigma) or
    ``uniform`` distribution, clipped to [min, max], in ascending order."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)


def stratified_order(n: int, rng, k: int = GROUP) -> np.ndarray:
    """A seeded order of the positions ``0..n-1`` of sorted values in which
    every run of ``k`` consecutive entries (from the first) holds one
    position from each of ``k`` strata, near-equal runs of the sorted
    positions; the last, shorter run holds one from each larger stratum."""
    stratum = np.arange(n) * k // n
    group = np.empty(n, np.int64)
    for j in range(k):
        idx = np.nonzero(stratum == j)[0]
        group[idx] = rng.permutation(len(idx))
    return np.lexsort((rng.random(n), group))


def stretches(mix: Dict, seconds: float) -> List[Tuple[float, float, int]]:
    """(start, length, requests) of each stretch of the traffic."""
    arr = mix["arrival"]
    if arr["kind"] == "poisson":
        spans = [(0.0, arr["ramp_s"]), (arr["ramp_s"], seconds),
                 (arr["ramp_s"] + seconds, arr["tail_s"])]
        return [(s, ln, int(round(arr["rate"] * ln))) for s, ln in spans]
    if arr["kind"] == "backlog":
        return [(0.0, 0.0, int(arr["requests"]))]
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def count(mix: Dict, seconds: float) -> int:
    """Requests a run of ``seconds`` needs."""
    return sum(n for _, _, n in stretches(mix, seconds))


def window_open(mix: Dict) -> Optional[float]:
    """Due-time at which the window opens, or None where the run decides
    (``after_turnover``)."""
    if mix["window"] == "after_ramp":
        return float(mix["arrival"]["ramp_s"])
    return None


def _stretch(mix: Dict, rng, start: float, length: float, n: int,
             sys_prompts: List[np.ndarray], vocab: int):
    user = _quantiles(mix["prompt"], n)
    system = np.full(n, -1)
    sp = mix.get("shared_prefix")
    if sp:
        # which user parts follow a system prompt is fixed over the sorted
        # sizes (evenly spread), so the prompt lengths too are one multiset
        i = np.arange(n)
        flag = np.floor((i + 1) * sp["share"]) > np.floor(i * sp["share"])
        system[flag] = rng.permutation(np.arange(flag.sum())
                                       % sp["n_prompts"])
    order = stratified_order(n, rng)
    user, system = user[order], system[order]
    out = _quantiles(mix["output"], n)[stratified_order(n, rng)]
    if length > 0:
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)[stratified_order(n, rng)]
        gaps *= length / gaps.sum()
        due = start + np.cumsum(gaps) - gaps / 2
    else:
        due = np.full(n, start)
    for u, s, o, d in zip(user, system, out, due):
        body = rng.integers(0, vocab, int(u), dtype=np.int32)
        if s >= 0:
            body = np.concatenate([sys_prompts[s], body])
        yield float(d), body, int(o), int(s)


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    rng = np.random.default_rng(int(seed) & ((1 << 64) - 1))
    sp = mix.get("shared_prefix")
    sys_prompts = ([rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
                    for _ in range(sp["n_prompts"])] if sp else [])
    reqs: List[Req] = []
    for start, length, n in stretches(mix, seconds):
        for due, body, out, s in _stretch(mix, rng, start, length, n,
                                         sys_prompts, vocab):
            reqs.append(Req(len(reqs), due, body, out, s))
    limit = mix["engine"]["max_len"]
    for r in reqs:
        assert len(r.prompt) + r.max_new <= limit, (r.id, len(r.prompt),
                                                    r.max_new, limit)
    return reqs
