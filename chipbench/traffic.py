"""One traffic generator for every mix file under ``chipbench/traffic/``.

A mix file is JSON:

``prompt_lens``     the finite set of prompt lengths, with ``prompt_weights``
``output_mean``     mean of the output length, ``1 + Geometric``, capped at
``output_cap``

Every cell is a closed loop at one slot: ``BACKLOG`` requests always wait
for the engine, whose cache holds ``MAX_SEQ`` positions.

Requests come in blocks of ``BLOCK``. Every block holds the same sizes: each
prompt length in proportion to its weight (largest remainders), and output
lengths at the ``BLOCK`` mid-quantiles of the capped ``1 + Geometric`` law.
A generator seeded with ``ORDER_SEED`` permutes both lists in each block. So
the sizes and their order are a function of the mix file alone, and every
run offers the same work; the run's seed draws the prompt tokens, uniform
over the vocabulary, as it draws the weights. The seeded draws follow
``repro.serving.workload.PoissonWorkload`` (geometric outputs, uniform
tokens).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BLOCK = 64  # requests per block of equal sizes
ORDER_SEED = 1  # fixes the order of the sizes inside each block
SLOTS = 1  # Engine.tick decodes every slot at one position (ROADMAP R1)
MAX_SEQ = 4096
BACKLOG = 1  # requests always waiting in the closed loop


@dataclass(frozen=True)
class Mix:
    name: str
    prompt_lens: tuple[int, ...]
    prompt_weights: tuple[float, ...]
    output_mean: float
    output_cap: int

    def __post_init__(self):
        if len(self.prompt_lens) != len(self.prompt_weights):
            raise ValueError(f"{self.name}: one weight per prompt length")
        if max(self.prompt_lens) + self.output_cap > MAX_SEQ:
            raise ValueError(f"{self.name}: prompt plus output exceeds {MAX_SEQ}")
        if not self.output_mean >= 1:
            raise ValueError(f"{self.name}: output_mean must be >= 1")


def load_mix(path: Path) -> Mix:
    raw = json.loads(Path(path).read_text())
    keys = {f for f in Mix.__dataclass_fields__ if f != "name"}
    if set(raw) != keys:
        raise ValueError(f"{path}: keys must be {sorted(keys)}, got {sorted(raw)}")
    return Mix(name=Path(path).stem, **{k: (tuple(v) if isinstance(v, list) else v)
                                        for k, v in raw.items()})


@dataclass(frozen=True)
class Draw:
    """One request as the generator made it."""

    index: int
    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int


def prompt_counts(mix: Mix, n: int) -> list[int]:
    """Per length, its share of ``n`` by largest remainders."""
    w = np.asarray(mix.prompt_weights, float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts.tolist()


def output_quantiles(mix: Mix, n: int) -> np.ndarray:
    """``1 + Geometric`` with mean ``output_mean`` at mid-quantiles, capped."""
    u = (np.arange(n) + 0.5) / n
    if mix.output_mean == 1:
        return np.ones(n, np.int64)
    p = 1.0 / mix.output_mean  # numpy's geometric: support 1.., mean 1/p
    k = np.ceil(np.log1p(-u) / np.log1p(-p)).astype(np.int64)
    return np.clip(k, 1, mix.output_cap)


def schedule(mix: Mix):
    """Endless ``(prompt_len, max_new_tokens)`` in blocks of ``BLOCK``."""
    rng = np.random.default_rng([ORDER_SEED, 1])
    lens = np.repeat(np.asarray(mix.prompt_lens), prompt_counts(mix, BLOCK))
    outs = output_quantiles(mix, BLOCK)
    while True:
        order = rng.permutation(lens), rng.permutation(outs)
        rng.permutation(BLOCK)  # a third draw per block: the order the bounds were measured on
        for L, o in zip(*order):
            yield int(L), int(o)


def requests(mix: Mix, seed: int, vocab: int):
    """Endless :class:`Draw` s: the mix's schedule, with prompt tokens drawn
    from ``seed``."""
    toks = np.random.default_rng([int(seed), 2])
    for i, (L, o) in enumerate(schedule(mix)):
        yield Draw(i, toks.integers(0, vocab, L, dtype=np.int32), o)


def expected_output(mix: Mix) -> float:
    """Mean of the capped ``1 + Geometric`` law (the tests hold the drawn
    outputs to it)."""
    p = 1.0 / mix.output_mean
    k = np.arange(1, mix.output_cap)
    return float(np.sum((1 - p) ** (k - 1) * p * k) + mix.output_cap * (1 - p) ** (mix.output_cap - 1))
