"""The benchmark's one traffic generator.

A traffic mix is a data file, ``bench/traffic/<mix>.json``:

    {"arrivals": "closed",
     "prompts": {"set": "<name>", ...}}      # bench/traffic/prompts/<name>.json

and a cell fixes the load: ``clients``, each sending its next request the
moment the previous one completes.  Everything is a pure function of the
seed, and every seed gets the same kind of work: themed prompts come in
blocks with the same count per theme (Zipf) and the same slot choices,
permuted; word prompts in blocks with the same mix of lengths.  A themed mix may fix the order itself with
``"order_seed"``: where only a few requests complete in a window, the
order decides how many groups fill, so it is the work, and every run
then gets the same stream (the run's seed still draws the weights, the
initial noise and the check's sample).

Prompt sets:

* ``themed``: ``themes`` of ``{"template", "slots"}``; theme k is drawn
  with weight 1/(k+1)^zipf; a prompt fills each slot with one option.
* ``words``: prompts of ``min_words``..``max_words`` words drawn at random
  from ``words`` (unrelated prompts).
"""
from __future__ import annotations

import itertools
import json
import pathlib
from typing import Iterator, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent / "traffic"
#: prompts come in blocks of this many (every theme at least once)
BLOCK = 64


def load_mix(name: str) -> dict:
    return json.loads((HERE / f"{name}.json").read_text())


def load_prompt_set(name: str) -> dict:
    return json.loads((HERE / "prompts" / f"{name}.json").read_text())


def _rng(seed: int, stream: int, block: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream, block])


def theme_prompts(theme: dict) -> List[str]:
    """Every prompt of one theme, in slot-product order."""
    return [theme["template"].format(*c)
            for c in itertools.product(*theme["slots"])]


def zipf_counts(n: int, s: float, total: int) -> List[int]:
    """Counts proportional to 1/(k+1)^s summing to ``total`` (largest
    remainder), each at least 1 when total >= n."""
    w = np.array([1.0 / (k + 1) ** s for k in range(n)])
    exact = w / w.sum() * total
    counts = np.maximum(np.floor(exact).astype(int), 1)
    while counts.sum() > total:
        counts[np.argmax(counts)] -= 1
    order = np.argsort(-(exact - np.floor(exact)), kind="stable")
    for k in itertools.cycle(order):
        if counts.sum() >= total:
            break
        counts[k] += 1
    return counts.tolist()


class Traffic:
    """The prompt stream of one cell under one seed."""

    def __init__(self, mix: dict, cell: dict, seed: int):
        self.mix, self.cell, self.seed = mix, cell, int(seed)
        self.kind = mix["arrivals"]
        if self.kind != "closed":
            raise ValueError(f"unknown arrivals {self.kind!r}")
        self.pspec = dict(mix["prompts"])
        self.pset = load_prompt_set(self.pspec["set"])

    # -- prompts ----------------------------------------------------------
    def _themed_block(self, b: int) -> List[str]:
        themes = self.pset["themes"]
        counts = zipf_counts(len(themes), float(self.pspec.get("zipf", 1.0)),
                             BLOCK)
        out = []
        for k, (theme, n) in enumerate(zip(themes, counts)):
            variants = theme_prompts(theme)
            # the same variants in every block: occurrence j of theme k
            # in block b takes variant (b * n + j) mod len
            out += [variants[(b * n + j) % len(variants)] for j in range(n)]
        order = int(self.pspec.get("order_seed", self.seed))
        perm = _rng(order, 1, b).permutation(len(out))
        return [out[i] for i in perm]

    def _words_block(self, b: int) -> List[str]:
        """Word prompts of a fixed size mix; the opening word runs through
        a seeded permutation of the list (the byte-level text tower's
        pooled vector leans on the opening bytes, so two prompts that
        open alike are near neighbours), the rest is drawn at random."""
        words = self.pset["words"]
        lo, hi = int(self.pspec["min_words"]), int(self.pspec["max_words"])
        sizes = [lo + i % (hi - lo + 1) for i in range(BLOCK)]
        rng = _rng(self.seed, 2, b)
        sizes = [sizes[i] for i in rng.permutation(BLOCK)]
        out = []
        for j, n in enumerate(sizes):
            k = b * BLOCK + j
            first = _rng(self.seed, 4, k // len(words)).permutation(
                len(words))[k % len(words)]
            rest = [i for i in rng.permutation(len(words))[:n] if i != first]
            out.append(" ".join(words[i] for i in [first] + rest[:n - 1]))
        return out

    def prompts(self) -> Iterator[str]:
        kind = self.pset["kind"]
        block = {"themed": self._themed_block,
                 "words": self._words_block}[kind]
        for b in itertools.count():
            yield from block(b)
