"""The one traffic generator: a mix file's parameters + a rate + a seed ->
the requests of a run. No JAX, no program import.

Every seed gets the same schedule: the same prompt lengths, output lengths,
prefix groups and inter-arrival gaps in the same order. The values are the
distribution's quantiles at N evenly spaced probabilities (so a run of N
requests *is* the distribution, not a noisy draw from it), shuffled once by a
fixed generator; the seed picks every token id (prompts, shared prefixes) and,
in agent_main.py, the weights. Arrivals are an open loop: gaps are the
exponential distribution's quantiles (a Poisson process's gaps), shuffled,
scaled to span the window exactly. (Measured, PERF.md section 6: with the
order drawn from the seed, `tpot_ms.p90` moved by 25% from seed to seed while
two runs of one seed agreed to 2%: the order of long and short answers is
work, and a benchmark's runs must do the same work.)

Mix file keys: `prompt_tokens`, `output_tokens` ({"dist": "lognormal",
"median", "sigma", "min", "max"} or {"dist": "uniform", "min", "max"});
`shared_prefix` (null, or {"groups", "tokens", "zipf_s"}: each request is
one group's fixed prefix + a private part of `prompt_tokens` length; each
group's prefix is sent once in set-up); `ramp_s` (seconds of the same
traffic before the measured window, not measured); `first_token_id`.
"""

from __future__ import annotations

import dataclasses
import json
import math
import random
import statistics
from pathlib import Path

MIX_KEYS = {"prompt_tokens", "output_tokens", "shared_prefix", "ramp_s",
            "first_token_id", "source", "why"}


@dataclasses.dataclass
class Request:
    rid: str
    due: float                 # seconds from the window's start (<0: ramp)
    prompt: list
    max_tokens: int
    group: int = -1            # shared-prefix group, -1 = none
    phase: str = "window"      # fill | ramp | window
    private_tokens: int = 0    # prompt tokens no other request shares
    logprobs: int = 0          # top-k logprobs asked with each token (0: none)


def read_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if set(mix) - MIX_KEYS or not {"prompt_tokens", "output_tokens",
                                   "ramp_s"} <= set(mix):
        raise ValueError(f"{path}: keys {sorted(mix)} (allowed {sorted(MIX_KEYS)})")
    return mix


def _quantile(spec: dict, u: float) -> int:
    if spec["dist"] == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        x = spec["median"] * math.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown dist {spec['dist']!r}")
    return int(min(spec["max"], max(spec["min"], round(x))))


def stratified(spec: dict, n: int) -> list[int]:
    return [_quantile(spec, (i + 0.5) / n) for i in range(n)]


def exp_gaps(n: int, span: float) -> list[float]:
    """n exponential-quantile gaps scaled to sum to `span`."""
    g = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = span / sum(g)
    return [x * k for x in g]


def zipf_counts(groups: int, s: float, n: int) -> list[int]:
    """How many of n requests go to each group: Zipf weights, largest
    remainders, summing to n exactly."""
    w = [1.0 / (r + 1) ** s for r in range(groups)]
    tot = sum(w)
    exact = [n * x / tot for x in w]
    counts = [int(e) for e in exact]
    for i in sorted(range(groups), key=lambda i: exact[i] - counts[i],
                    reverse=True)[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _phase(mix, rate, span, t0, rng, vocab, prefixes, phase, tag):
    n = max(1, round(rate * span))
    plen = stratified(mix["prompt_tokens"], n)
    olen = stratified(mix["output_tokens"], n)
    gaps = exp_gaps(n, span)
    order = random.Random(f"chipbench-order-{phase}")   # the same for every seed
    order.shuffle(plen), order.shuffle(olen), order.shuffle(gaps)
    sp = mix.get("shared_prefix")
    if sp:
        groups = [g for g, c in enumerate(
            zipf_counts(sp["groups"], sp["zipf_s"], n)) for _ in range(c)]
        order.shuffle(groups)
    else:
        groups = [-1] * n
    lo = mix.get("first_token_id", 256)
    out, t = [], t0
    for i in range(n):
        private = [rng.randrange(lo, vocab) for _ in range(plen[i])]
        prompt = (prefixes[groups[i]] if sp else []) + private
        out.append(Request(f"{tag}{i}", t, prompt, olen[i], groups[i],
                           phase, plen[i]))
        t += gaps[i]
    return out


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Request]:
    """All requests of one run: set-up fills (due = -inf order, sent before
    anything is timed), the ramp, the measured window."""
    rng = random.Random(f"chipbench-{seed}")
    sp = mix.get("shared_prefix")
    lo = mix.get("first_token_id", 256)
    prefixes = []
    fills = []
    if sp:
        prefixes = [[rng.randrange(lo, vocab) for _ in range(sp["tokens"])]
                    for _ in range(sp["groups"])]
        tail = mix["prompt_tokens"]["min"]
        for g, pre in enumerate(prefixes):
            private = [rng.randrange(lo, vocab) for _ in range(tail)]
            fills.append(Request(f"f{g}", float("-inf"), pre + private, 2, g,
                                 "fill", tail))
    ramp_s = float(mix["ramp_s"])
    ramp = _phase(mix, rate, ramp_s, -ramp_s, rng, vocab, prefixes,
                  "ramp", "r") if ramp_s > 0 else []
    window = _phase(mix, rate, float(seconds), 0.0, rng, vocab, prefixes,
                    "window", "w")
    return fills + ramp + window


def longest_total(mix: dict) -> int:
    sp = mix.get("shared_prefix")
    return ((sp["tokens"] if sp else 0) + mix["prompt_tokens"]["max"]
            + mix["output_tokens"]["max"])


def prefill_ranges(mix: dict) -> list[tuple[int, int]]:
    """(shortest, longest) of the token counts a prefill may see: the
    private part alone (prefix served from the cache) and, where a prefix
    is shared, the whole prompt (cold, or evicted)."""
    sp = mix.get("shared_prefix")
    pt = mix["prompt_tokens"]
    out = [(pt["min"], pt["max"])]
    if sp:
        out.append((sp["tokens"] + pt["min"], sp["tokens"] + pt["max"]))
    return out
