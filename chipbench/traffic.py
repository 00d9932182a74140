"""One general generator for every traffic mix (``traffic/<mix>.json``).

A mix is data: the arrival process, a grid of prompt lengths with
lognormal weights, a lognormal output-length distribution with clipping,
and a token-id distribution. The generator turns a mix and a seed into a
stream of requests.

Steadiness across seeds: the sizes and their order belong to the mix,
as in a replayed trace, and the seed draws only the token ids. The mix
fixes ``POOL`` prompt lengths and ``POOL`` output lengths, each sorted
and cut into ``BLOCK`` equal strata. Requests come in blocks of
``BLOCK``: block ``b`` takes from every stratum the member of rank
``v(b)`` within it, ``v`` being the bit reversal of ``b`` (so that the
early blocks spread over each stratum), and pairs and orders them by a
scrambled two-dimensional Sobol net (``net_order``), which keeps each
aligned run of ``2**m`` requests of a block stratified in both lengths.
A window, which serves only the first few blocks, thus serves sizes that
follow the mix's distributions, and the same ones on every seed. An
order drawn from the seed changes the work a window does (``PERF.md``,
section 6), so the net is scrambled from the mix's own fixed seed.
``POOL`` requests use every prompt and output length once, and every
prompt length of the grid appears (the prefill programs the warm-up
compiled).

Arrivals: ``{"kind": "backlog"}`` is the only process so far: the harness
keeps at least ``slots`` requests queued, and a request is due when it is
queued. A timed process comes with the first cell that offers load below
saturation.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator

import numpy as np

HERE = Path(__file__).resolve().parent
BITS = 6
BLOCK = 1 << BITS    # requests in a block, and strata of each length
POOL = BLOCK * BLOCK  # prompt lengths, and output lengths, a mix fixes
_MIX_SEED = 20250108  # fixed: the order of sizes belongs to the mix


def load(name: str) -> Dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def prompt_grid(p: Dict) -> np.ndarray:
    """``grid_n`` distinct log-spaced prompt lengths in [grid_min, grid_max]."""
    g = np.unique(np.round(np.geomspace(p["grid_min"], p["grid_max"],
                                        p["grid_n"])).astype(np.int64))
    if len(g) != p["grid_n"]:
        raise ValueError(f"prompt grid collapses to {len(g)} lengths")
    return g


def grid_weights(p: Dict, grid: np.ndarray) -> np.ndarray:
    """Lognormal weights (median, sigma) on a log-spaced grid."""
    z = (np.log(grid) - math.log(p["median"])) / p["sigma"]
    w = np.exp(-0.5 * z * z)
    return w / w.sum()


def _apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """Largest-remainder integer counts summing to ``n``."""
    raw = weights * n
    c = np.floor(raw).astype(np.int64)
    c[np.argsort(-(raw - c), kind="stable")[: n - c.sum()]] += 1
    return c


def size_levels(mix: Dict, n: int = POOL):
    """The mix's fixed ``n`` prompt lengths and ``n`` output lengths, each
    sorted: prompts on the grid in proportion to its weights, outputs the
    lognormal's ``n`` quantiles, rounded and clipped."""
    p, o = mix["prompt"], mix["output"]
    grid = prompt_grid(p)
    prompts = np.repeat(grid, _apportion(grid_weights(p, grid), n))
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    outs = np.clip(np.round(o["median"] * np.exp(o["sigma"] * z)),
                   o["min"], o["max"]).astype(np.int64)
    return prompts, outs


def _pascal(bits: int) -> np.ndarray:
    """Sobol's second generator matrix: binomial coefficients mod 2."""
    i, j = np.ogrid[:bits, :bits]
    return ((j >= i) & ((j & i) == i)).astype(np.int64)


def net_order(rng: np.random.Generator, bits: int = BITS) -> np.ndarray:
    """``(2**bits, 2)`` strata: row ``k`` is the k-th request's (prompt
    stratum, output stratum). The two-dimensional Sobol sequence (generator matrices
    the identity and Pascal's triangle mod 2) is a (0, 2)-sequence in base
    2: each aligned run of ``2**m`` points is a (0, m, 2)-net. A random
    lower unit-triangular matrix and a random digital shift per dimension
    (Matousek's linear scrambling) keep that property, so every seed's
    order is stratified alike; each column is a permutation of
    ``range(2**bits)``."""
    k = np.arange(1 << bits)
    digits = (k[:, None] >> np.arange(bits)) & 1        # least significant first
    weight = 1 << np.arange(bits - 1, -1, -1)           # output digit 0 is the top
    cols = []
    for gen in (np.eye(bits, dtype=np.int64), _pascal(bits)):
        low = np.tril(rng.integers(0, 2, (bits, bits)), -1) + np.eye(
            bits, dtype=np.int64)
        shift = rng.integers(0, 2, bits)
        y = (digits @ ((low @ gen) % 2).T + shift) % 2
        cols.append(y @ weight)
    return np.stack(cols, 1)


class TokenSampler:
    """Token ids for one run: Zipf over a seed-permuted vocabulary."""

    def __init__(self, spec: Dict, vocab: int, rng: np.random.Generator):
        if spec["kind"] != "zipf":
            raise ValueError(f"unknown token distribution {spec['kind']!r}")
        self.rng = rng
        self.perm = rng.permutation(vocab)
        w = np.arange(1, vocab + 1, dtype=float) ** -spec["s"]
        self.cdf = np.cumsum(w / w.sum())

    def draw(self, length: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, self.rng.random(length),
                                side="right")
        ranks = np.minimum(ranks, len(self.cdf) - 1)
        return self.perm[ranks].astype(np.int32)


@dataclass
class Request:
    prompt: np.ndarray
    max_new_tokens: int


def stream(mix: Dict, vocab: int, seed: int) -> Iterator[Request]:
    """The endless request stream of a backlog ``mix`` for run ``seed``."""
    if mix["arrivals"]["kind"] != "backlog":
        raise ValueError(
            f"unknown arrival process {mix['arrivals']['kind']!r}")
    prompts, outs = size_levels(mix)
    tokens = TokenSampler(mix["tokens"], vocab, np.random.default_rng(seed))
    order = np.random.default_rng(_MIX_SEED)
    rev = [int(f"{b:0{BITS}b}"[::-1], 2) for b in range(BLOCK)]
    b = 0
    while True:
        v = rev[b % BLOCK]
        for i, j in net_order(order):
            yield Request(tokens.draw(int(prompts[i * BLOCK + v])),
                          int(outs[j * BLOCK + v]))
        b += 1


def longest_live(mix: Dict) -> int:
    """The most cache rows one request can hold: longest prompt plus
    longest output."""
    return int(prompt_grid(mix["prompt"]).max() + mix["output"]["max"])
