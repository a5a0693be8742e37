"""The traffic generator: sizes from the mix file, tokens from the seed;
sizes distributed as the mix file says."""

import itertools
import json
from collections import Counter

import numpy as np
import pytest

from chipbench import harness
from chipbench import traffic as T

MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**33 + 12345


def _key(draws):
    return [(d.max_new_tokens, d.prompt.tobytes()) for d in draws]


def _sizes(draws):
    return [(len(d.prompt), d.max_new_tokens) for d in draws]


def _take(mix, seed, n, vocab=500):
    return list(itertools.islice(T.requests(mix, seed, vocab), n))


def test_every_mix_of_the_benchmark_is_here():
    spec = harness.load_spec()
    assert {c["traffic"] for c in spec["workloads"]} <= set(MIXES)


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_a_function_of_the_seed(name):
    mix = harness.load_mix(name)
    a, b, c = (_take(mix, s, 3 * T.BLOCK) for s in (BIG_SEED, BIG_SEED, BIG_SEED + 1))
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # every seed offers the same sizes in the same order; only the tokens differ
    assert _sizes(a) == _sizes(c)
    assert all(np.all((d.prompt >= 0) & (d.prompt < 500)) for d in a + c)


@pytest.mark.parametrize("name", MIXES)
def test_every_block_holds_the_same_sizes(name):
    mix = harness.load_mix(name)
    draws = _take(mix, 3, 3 * T.BLOCK)
    blocks = [draws[k * T.BLOCK:(k + 1) * T.BLOCK] for k in range(3)]
    lens = [Counter(len(d.prompt) for d in b) for b in blocks]
    outs = [sorted(d.max_new_tokens for d in b) for b in blocks]
    assert lens[0] == lens[1] == lens[2]
    assert outs[0] == outs[1] == outs[2]
    assert [len(d.prompt) for d in blocks[0]] != [len(d.prompt) for d in blocks[1]]
    assert lens[0] == Counter(dict(zip(mix.prompt_lens, T.prompt_counts(mix, T.BLOCK))))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix_file(name):
    mix = harness.load_mix(name)
    n = 4000
    counts = T.prompt_counts(mix, n)
    w = np.asarray(mix.prompt_weights, float)
    assert sum(counts) == n
    assert np.all(np.abs(np.asarray(counts) - w / w.sum() * n) < 1)
    outs = T.output_quantiles(mix, n)
    assert outs.min() >= 1 and outs.max() <= mix.output_cap
    assert abs(outs.mean() - T.expected_output(mix)) < 0.02 * mix.output_mean
    # against a seeded Monte Carlo draw of the same capped 1 + Geometric law
    mc = np.minimum(np.random.default_rng(0).geometric(1 / mix.output_mean, 200_000),
                    mix.output_cap)
    assert abs(outs.mean() - mc.mean()) < 0.03 * mix.output_mean
    # one block's outputs keep the law's mean within a tenth
    assert abs(T.output_quantiles(mix, T.BLOCK).mean() / T.expected_output(mix) - 1) < 0.1
    assert max(mix.prompt_lens) + mix.output_cap <= T.MAX_SEQ


def test_a_mix_that_cannot_fit_the_cache_is_refused():
    with pytest.raises(ValueError):
        T.Mix(name="x", prompt_lens=(4000,), prompt_weights=(1,), output_mean=10,
              output_cap=200)


def test_a_mix_file_with_other_keys_is_refused(tmp_path):
    path = tmp_path / "x.json"
    path.write_text(json.dumps({"prompt_lens": [8], "prompt_weights": [1], "output_mean": 4,
                                "output_cap": 8, "slots": 2}))
    with pytest.raises(ValueError):
        T.load_mix(path)
