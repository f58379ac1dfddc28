"""One general traffic generator, driven by a mix file ``traffic/<mix>.json``.

A mix fixes its arrival process, rate, length distributions and rounding.
Sizes, arrival times and which size lands on which arrival come from the
mix's own ``schedule_seed``, so every run of a cell serves the same sizes
on the same schedule; the run's ``--seed`` draws the token ids. The work
therefore does not change with the seed, only its content.

Arrival process (copied from ``repro.serving.loadgen.arrivals`` so the
yardstick cannot move with the program):

  poisson  ``rate_rps``: exponential gaps

Lengths are clamped log-normals (``median``, ``sigma``, ``min``, ``max``);
prompts are then rounded up to a multiple of ``round_to`` tokens.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Item:
    """One request of a run: its scheduled arrival and its sizes."""
    index: int
    offset_s: float
    prompt: np.ndarray          # (prompt_len,) int32
    max_new: int

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def load_mix(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


# -- arrivals (copied from repro.serving.loadgen.arrivals) ------------------ #
def poisson_arrivals(rate_rps: float, duration_s: float,
                     seed: int = 0) -> List[float]:
    if rate_rps <= 0 or duration_s <= 0:
        return []
    rng = np.random.default_rng(seed)
    out: List[float] = []
    t = 0.0
    while True:
        n = max(int(rate_rps * duration_s
                    + 4 * np.sqrt(rate_rps * duration_s)) + 1, 16)
        for gap in rng.exponential(1.0 / rate_rps, size=n):
            t += float(gap)
            if t >= duration_s:
                return out
            out.append(t)


def arrival_offsets(mix: Dict, seconds: float) -> List[float]:
    arr = mix["arrivals"]
    seed = int(mix.get("schedule_seed", 0))
    kind = arr["process"]
    if kind == "poisson":
        return poisson_arrivals(float(arr["rate_rps"]), seconds, seed)
    raise ValueError(f"unknown arrival process {kind!r}")


# -- lengths ------------------------------------------------------------------ #
def clamped_lognormal(rng: np.random.Generator, n: int, median: float,
                      sigma: float, lo: int, hi: int) -> np.ndarray:
    ln = rng.lognormal(math.log(median), sigma, size=n)
    return np.clip(np.rint(ln).astype(np.int64), lo, hi)


def round_up(n: np.ndarray, to: int) -> np.ndarray:
    return -(-np.asarray(n) // to) * to if to > 1 else np.asarray(n)


def sizes(mix: Dict, n: int) -> List[Tuple[int, int]]:
    """(prompt_len, max_new) for ``n`` requests, from the mix's
    ``schedule_seed`` alone (the same for every run seed). Prompt and
    output lengths draw from streams of their own, so the sizes of ``n``
    requests are the first ``n`` of any longer run's: a run at a higher
    rate serves the same requests, closer together."""
    seed = int(mix.get("schedule_seed", 0))
    p, o = mix["prompt"], mix["output"]
    plen = clamped_lognormal(np.random.default_rng([seed, 1]), n,
                             p["median"], p["sigma"], p["min"], p["max"])
    plen = round_up(plen, int(p.get("round_to", 1)))
    olen = clamped_lognormal(np.random.default_rng([seed, 2]), n,
                             o["median"], o["sigma"], o["min"], o["max"])
    return [(int(a), int(b)) for a, b in zip(plen, olen)]


def max_prompt_len(mix: Dict) -> int:
    p = mix["prompt"]
    return int(round_up(np.asarray(p["max"]), int(p.get("round_to", 1))))


def max_seq_len(mix: Dict) -> int:
    return max_prompt_len(mix) + int(mix["output"]["max"])


def build(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests of one run: schedule and sizes from the mix, token ids
    from ``seed`` (any non-negative integer)."""
    offsets = arrival_offsets(mix, seconds)
    sz = sizes(mix, len(offsets))
    tok_rng = np.random.default_rng([seed, 0x70C])
    items = []
    for i, off in enumerate(offsets):
        plen, new = sz[i]
        prompt = tok_rng.integers(0, vocab, plen).astype(np.int32)
        items.append(Item(i, float(off), prompt, new))
    return items
