"""The one traffic generator.  A traffic mix is a JSON file under
``bench/traffic/``; this module turns it and a seed into requests.

Every seed gets the same work.  Each length, arrival gap, prefix group and
tenant is drawn as a stratified block: a block of ``block`` requests holds
the block's quantiles of each distribution exactly once, in an order that
the mix's own ``schedule_seed`` permutes.  The run's seed draws the token
ids (the system prompts and every user turn).  So two seeds differ in what
the requests say, not in how much work a window holds or when it comes.

A mix's ``kind`` is ``open`` (arrivals on a Poisson schedule at a fixed
rate, sent whether or not the server keeps up) or ``closed`` (a backlog
kept at a fixed depth: a new request is queued as each one completes).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class RequestSpec:
    """One request as the generator makes it.  ``due`` is seconds after the
    start of the stream it belongs to (0 for a closed backlog)."""
    index: int
    due: float
    prompt: List[int]
    max_new_tokens: int
    tenant: str
    group: int = -1          # prefix group, -1 for none
    repeat_of: int = -1      # index of the request whose prompt this repeats


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words for numpy's SeedSequence."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream name)."""
    salt = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(seed_words(seed) + salt))


def lognormal_quantiles(spec: Dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of a clipped log-normal length distribution:
    ``median``, ``sigma`` (of the log), ``min``, ``max``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(spec["max"], max(spec["min"], round(v)))))
    return out


def exponential_quantiles(rate: float, n: int) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def share_counts(weights: List[float], n: int) -> List[int]:
    """Split ``n`` slots among categories in proportion to ``weights``
    (largest remainders), so every block holds the same mix."""
    total = float(sum(weights))
    exact = [w * n / total for w in weights]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(weights)), key=lambda i: counts[i] - exact[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def zipf_weights(n: int, s: float) -> List[float]:
    return [1.0 / (k + 1) ** s for k in range(n)]


def _stratified(rng: np.random.Generator, values: List, n_blocks: int) -> List:
    out: List = []
    for _ in range(n_blocks):
        out.extend(values[i] for i in rng.permutation(len(values)))
    return out


def generate(mix: Dict, seed: int, n: int, vocab: int,
             stream: str = "window") -> List[RequestSpec]:
    """The first ``n`` requests of ``mix`` for ``seed``.  ``stream`` names
    an independent stream of the same mix."""
    block = int(mix["block"])
    n_blocks = -(-n // block)
    rng = rng_for(int(mix["schedule_seed"]), stream)
    tok_rng = rng_for(seed, stream + ".tokens")

    outs = _stratified(rng, lognormal_quantiles(mix["output"], block), n_blocks)
    tenants = [t["name"] for t in mix["tenants"]]
    per_tenant = share_counts([t["share"] for t in mix["tenants"]], block)
    tenant_slots = [name for name, c in zip(tenants, per_tenant)
                    for _ in range(c)]
    tenant_of = _stratified(rng, tenant_slots, n_blocks)

    prefix = mix.get("prefix")
    if prefix:
        counts = share_counts(zipf_weights(prefix["groups"], prefix["zipf"]),
                              block)
        group_slots = [g for g, c in enumerate(counts) for _ in range(c)]
        group_of = _stratified(rng, group_slots, n_blocks)
        # system prompts: one fixed token sequence per group for this seed
        prefixes = [tok_rng.integers(0, vocab, prefix["tokens"]).tolist()
                    for _ in range(prefix["groups"])]
        body = _stratified(rng, lognormal_quantiles(mix["suffix"], block),
                           n_blocks)
    else:
        group_of = [-1] * (n_blocks * block)
        prefixes = []
        body = _stratified(rng, lognormal_quantiles(mix["prompt"], block),
                           n_blocks)

    if mix["kind"] == "open":
        gaps = _stratified(rng, exponential_quantiles(mix["rate_per_s"], block),
                           n_blocks)
        dues = np.cumsum(gaps).tolist()
    else:
        dues = [0.0] * (n_blocks * block)

    # requests that repeat a recent request's whole prompt (a user asking
    # for another answer): a fixed number per block, at seeded places
    repeats = int(mix.get("repeats_per_block", 0))
    repeat_at = set()
    for b in range(n_blocks):
        places = rng.choice(np.arange(1, block), size=repeats, replace=False)
        repeat_at.update(int(b * block + p) for p in places)

    reqs: List[RequestSpec] = []
    for i in range(n):
        if i in repeat_at:
            src = reqs[i - 1 - int(rng.integers(0, min(3, i)))]
            while src.repeat_of >= 0:
                src = reqs[src.repeat_of]
            prompt, group, repeat_of = list(src.prompt), src.group, src.index
        else:
            g = group_of[i]
            suffix = tok_rng.integers(0, vocab, body[i]).tolist()
            prompt = (prefixes[g] if g >= 0 else []) + suffix
            group, repeat_of = g, -1
        reqs.append(RequestSpec(index=i, due=float(dues[i]), prompt=prompt,
                                max_new_tokens=int(outs[i]),
                                tenant=tenant_of[i], group=group,
                                repeat_of=repeat_of))
    return reqs


def requests_for_window(mix: Dict, seconds: float) -> int:
    """How many requests an open mix needs to cover ``seconds``: a few
    blocks more than the rate asks, so the window never runs dry."""
    if mix["kind"] != "open":
        return int(mix["requests"])
    return int(math.ceil(mix["rate_per_s"] * seconds)) + 2 * int(mix["block"])
