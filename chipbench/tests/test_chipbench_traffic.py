"""The traffic generator: deterministic per seed, the same sizes for every
seed, and distributions that match their parameters."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

MIXES = sorted(p.stem for p in
               (Path(traffic.__file__).parent / "traffic").glob("*.json"))
BIG = 2 ** 33 + 12345   # seeds exceed 32 bits


def head(mix, seed, n, vocab=50257):
    s = traffic.stream(mix, vocab, seed)
    return [next(s) for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a, b = head(mix, BIG, 40), head(mix, BIG, 40)
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = head(mix, BIG + 1, 40)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_draws_from_the_grid_and_the_same_sizes(name):
    mix = traffic.load(name)
    grid = set(traffic.prompt_grid(mix["prompt"]).tolist())
    assert len(grid) == mix["prompt"]["grid_n"]
    blocks = []
    for seed in (0, 7, BIG):
        reqs = head(mix, seed, traffic.POOL)
        assert {len(r.prompt) for r in reqs} == grid
        assert all(mix["output"]["min"] <= r.max_new_tokens
                   <= mix["output"]["max"] for r in reqs)
        blocks.append([(Counter(len(r.prompt) for r in b),
                        Counter(r.max_new_tokens for r in b))
                       for b in (reqs[k:k + traffic.BLOCK] for k in
                                 range(0, traffic.POOL, traffic.BLOCK))])
    # block by block, every seed serves the same prompt and output lengths
    assert blocks[0] == blocks[1] == blocks[2]
    # in the same order: the seed draws the token ids alone
    assert len({tuple((len(r.prompt), r.max_new_tokens)
                      for r in head(mix, seed, 300))
                for seed in (0, 7, BIG)}) == 1
    prompts, outs = traffic.size_levels(mix)
    assert sum((b[0] for b in blocks[0]), Counter()) == Counter(
        prompts.tolist())
    assert sum((b[1] for b in blocks[0]), Counter()) == Counter(
        outs.tolist())


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_net_order_stratifies_every_aligned_run(seed):
    bits = traffic.BITS
    order = traffic.net_order(np.random.default_rng(seed))
    for col in order.T:
        assert sorted(col.tolist()) == list(range(1 << bits))
    for m in range(bits + 1):
        for start in range(0, 1 << bits, 1 << m):
            run = order[start:start + (1 << m)]
            for a in range(m + 1):
                boxes = set(zip((run[:, 0] >> (bits - a)).tolist(),
                                (run[:, 1] >> (bits - m + a)).tolist()))
                assert len(boxes) == len(run), (m, start, a)


def test_grid_weights_follow_the_lognormal_median():
    p = {"grid_min": 16, "grid_max": 704, "grid_n": 24, "median": 192,
         "sigma": 0.9}
    grid = traffic.prompt_grid(p)
    w = traffic.grid_weights(p, grid)
    assert w.sum() == pytest.approx(1.0)
    assert abs(grid[np.argmax(w)] - 192) < 192 * 0.2
    prompts, outs = traffic.size_levels({"prompt": p, "output": {
        "median": 128, "sigma": 0.6, "min": 8, "max": 256}})
    assert np.median(outs) == 128
    assert abs(np.log(np.median(prompts) / 192)) < 0.2


def test_zipf_ids_follow_their_exponent():
    rng = np.random.default_rng(BIG)
    ts = traffic.TokenSampler({"kind": "zipf", "s": 1.0}, 1000, rng)
    ids = ts.draw(200_000)
    assert ids.min() >= 0 and ids.max() < 1000
    counts = np.bincount(ids, minlength=1000)[ts.perm]
    # rank r is drawn in proportion to r**-s
    assert counts[0] / counts[1] == pytest.approx(2.0, rel=0.05)
    assert counts[0] / counts[9] == pytest.approx(10.0, rel=0.1)


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_names_its_source_and_fits_the_cache(name):
    mix = traffic.load(name)
    assert mix["source"] and mix["derivation"]
    assert traffic.longest_live(mix) <= 1024


def test_unknown_processes_are_errors():
    mix = dict(traffic.load(MIXES[0]))
    with pytest.raises(ValueError):
        next(traffic.stream(dict(mix, arrivals={"kind": "poisson"}), 100, 1))
    with pytest.raises(ValueError):
        next(traffic.stream(dict(mix, tokens={"kind": "topics"}), 100, 1))
