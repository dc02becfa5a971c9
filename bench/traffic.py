"""The one generator of traffic: it reads a mix's parameters from
`traffic/<mix>.json` and makes the requests or batches from `--seed`.

Every seed gets the same set of sizes and arrival gaps, in another order:
lengths are drawn as exact shares of the stated weights and as quantiles of
the stated distributions, and only their order and the token ids depend on
the seed.  So runs with different seeds do the same amount of work, and the
spread between them is the system's own.

Kinds of mix:
  train          batches of uniform random tokens, `batch_per_chip` rows of
                 `seq` tokens per chip, with next-token labels.
  serve_open     an open loop: requests due at Poisson arrival times on the
                 host clock at `rate_per_s`, whatever the server does.
  serve_backlog  a batch job: the queue is kept `backlog_factor` x slots deep,
                 so the server always has work.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

__all__ = ["KINDS", "Req", "load_mix", "prompt_tokens", "request_stream"]

KINDS = ("train", "serve_open", "serve_backlog")
MIX_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = MIX_DIR) -> Dict:
    path = directory / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r} is not one of {KINDS}")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def shares(values: List[int], weights: List[float], n: int) -> List[int]:
    """n values in exact proportion to `weights` (largest remainders)."""
    if len(values) != len(weights):
        raise ValueError(f"{len(values)} values but {len(weights)} weights")
    w = np.asarray(weights, np.float64) / np.sum(weights)
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return [v for v, c in zip(values, counts) for _ in range(c)]


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """n evenly spaced quantiles of a length distribution, clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(int)


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    prompt_len: int
    max_new_tokens: int
    due_s: float  # offset from the window's start (0 for backlog requests)


def _cycle(mix: Dict, seed: int, c: int):
    """Cycle c of a serving mix: (prompt_len, max_new_tokens, gap_s) triples,
    the same multiset for every seed, in the seed's order."""
    cycle = mix["cycle"]
    rng = _rng(seed, 1, c)
    lens = shares(mix["prompt_lens"], mix["prompt_weights"], cycle)
    outs = list(quantiles(mix["output"], cycle))
    lens = [lens[i] for i in rng.permutation(cycle)]
    outs = [outs[i] for i in rng.permutation(cycle)]
    gaps = np.zeros(cycle)
    if mix["kind"] == "serve_open":
        u = (np.arange(cycle) + 0.5) / cycle
        gaps = (-np.log1p(-u) / mix["rate_per_s"])[rng.permutation(cycle)]
    return zip(lens, outs, gaps)


def request_stream(mix: Dict, seed: int) -> Iterator[Req]:
    """The mix's requests for `seed`, cycle after cycle, without end."""
    t, i = 0.0, 0
    for c in itertools.count():
        for p, o, g in _cycle(mix, seed, c):
            t += float(g)
            yield Req(i, int(p), int(o), t)
            i += 1


def prompt_tokens(seed: int, req: Req, vocab: int) -> np.ndarray:
    return _rng(seed, 2, req.index).integers(0, vocab, req.prompt_len).astype(np.int32)
