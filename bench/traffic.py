"""The one traffic generator: turns a mix's data file into requests.

A mix is a JSON file under ``bench/mixes/``; a cell's own numbers (its
fixed rate) are in ``bench/cells/<workload>.json``. Every seed replays
the same schedule: the sizes, the shared prompt each request starts
with and the arrival instants are drawn once from the mix's
``size_seed``; ``--seed`` draws the token ids. So two seeds do the same
work at the same instants, and the spread between seeds is the
system's, not the traffic's. (With the order drawn from the seed, the
90th percentile of some forty TTFTs was decided by which long prompts
happened to arrive together, and swung by a factor of 2.7 from seed to
seed on a TPU v5e.)

Kinds:

- ``open_loop``: Poisson arrivals at ``rate_per_s``, exactly
  ``round(rate * seconds)`` requests due inside the window. A prompt is
  one of the shared system prompts (Zipf over ``count``) followed by a
  unique user part; lengths are lognormal, clipped and rounded.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    """What the engine is given. ``arrival_s`` is the due instant,
    relative to the start of the window."""

    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: float
    system: int                     # which shared system prompt (-1: none)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for (seed, stream...): any whole seed, of any size."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def lognormal_lengths(r: np.random.Generator, n: int, spec: dict
                      ) -> np.ndarray:
    """``n`` lognormal lengths with the spec's median and sigma, clipped
    to [min, max] and rounded up to ``round_to``."""
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * r.standard_normal(n))
    x = np.clip(x, spec["min"], spec["max"])
    step = spec.get("round_to", 1)
    return (np.ceil(x / step) * step).astype(np.int64)


def zipf_choices(r: np.random.Generator, n: int, count: int, s: float
                 ) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return r.choice(count, size=n, p=w / w.sum())


def system_prompts(mix: dict, vocab: int, seed: int) -> List[np.ndarray]:
    sp = mix["system_prompts"]
    r = rng(seed, 1)
    return [r.integers(3, vocab, sp["tokens"], dtype=np.int64).astype(np.int32)
            for _ in range(sp["count"])]


def open_loop(mix: dict, *, rate: float, seconds: float, vocab: int,
              seed: int) -> List[Request]:
    """The window's requests, sorted by arrival."""
    n = max(int(round(rate * seconds)), 1)
    fixed = rng(mix["size_seed"], n)
    user = lognormal_lengths(fixed, n, mix["user_tokens"])
    out = lognormal_lengths(fixed, n, mix["output_tokens"])
    sp = mix["system_prompts"]
    which = zipf_choices(fixed, n, sp["count"], sp["zipf_s"])
    gaps = fixed.exponential(1.0, n + 1)
    arrivals = np.cumsum(gaps * (seconds / gaps.sum()))[:n]

    r = rng(seed, 0)
    systems = system_prompts(mix, vocab, seed)
    reqs = []
    for i in range(n):
        body = r.integers(3, vocab, int(user[i]), dtype=np.int64)
        prompt = np.concatenate([systems[which[i]], body.astype(np.int32)])
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(out[i]),
                            arrival_s=float(arrivals[i]),
                            system=int(which[i])))
    return reqs


def max_context(mix: dict) -> int:
    """The longest prompt plus answer the mix can produce."""
    return (mix["system_prompts"]["tokens"] + mix["user_tokens"]["max"]
            + mix["output_tokens"]["max"])


def chunk_remainders(mix: dict, chunk: int) -> List[int]:
    """Every prefill-chunk length an admit can run: the user part starts
    on a page boundary after a shared system prompt, and is a multiple of
    ``round_to``, so the chunks are full ones and one remainder."""
    step = mix["user_tokens"].get("round_to", 1)
    sizes = {chunk}
    for u in range(mix["user_tokens"]["min"], mix["user_tokens"]["max"] + 1,
                   step):
        if u % chunk:
            sizes.add(u % chunk)
    # a cold system prompt is computed with the first user tokens
    first = mix["system_prompts"]["tokens"]
    for u in range(mix["user_tokens"]["min"], mix["user_tokens"]["max"] + 1,
                   step):
        if (first + u) % chunk:
            sizes.add((first + u) % chunk)
    return sorted(sizes)


GENERATORS = {"open_loop": open_loop}
