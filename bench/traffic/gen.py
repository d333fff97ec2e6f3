"""The benchmark's own traffic generator, driven by a mix's data file.

It does not use the program's data pipeline, so a change to the program
cannot change the yardstick. Every draw is a pure function of the seed
and an index, so the same seed gives the same inputs.

Train mixes (``driver: train``): packed documents with lognormal lengths
and EOS between them; token ids Zipf(``zipf_s``) over the vocabulary
without EOS, mapped through a permutation drawn from the seed (real text
follows Zipf's law, and hot tokens load the same experts); labels are
the next token, masked (-100) where the next token is EOS.

Serve mixes (``driver: serve``): batches of ``batch`` prompts of one
length (the program keeps one cache length per batch), lengths dealt from
a deck in the same order for every seed, so every cycle of ``sum(deck)``
batches holds the same mix and a window of a given length ends at the
same place in the deck whatever the seed. Prompt ids are Zipf like the
train mixes.
"""

from __future__ import annotations

import numpy as np

_MASK64 = (1 << 64) - 1


def rng(seed: int, *index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & _MASK64, *index])


class Zipf:
    """Token ids 1..V-1 with P(rank r) proportional to r**-s."""

    def __init__(self, seed: int, vocab: int, s: float):
        ranks = np.arange(1, vocab, dtype=np.float64)
        p = ranks ** -s
        self.cdf = np.cumsum(p / p.sum())
        self.ids = rng(seed, 0).permutation(vocab - 1).astype(np.int32) + 1

    def draw(self, g: np.random.Generator, shape) -> np.ndarray:
        r = np.searchsorted(self.cdf, g.random(shape), side="right")
        return self.ids[np.minimum(r, len(self.ids) - 1)]


def train_batch(mix: dict, vocab: int, seed: int, index: int,
                zipf: Zipf | None = None) -> dict[str, np.ndarray]:
    """Batch ``index`` of a train mix: int32 tokens and labels (B, S)."""
    zipf = zipf or Zipf(seed, vocab, mix["zipf_s"])
    B, S, eos = mix["batch"], mix["seq_len"], mix["eos_id"]
    g = rng(seed, 1, index)
    rows = np.empty((B, S + 1), np.int32)
    for b in range(B):
        toks = zipf.draw(g, S + 1)
        pos = int(g.integers(0, 64))       # where the first document ends
        while pos < S + 1:
            toks[pos] = eos
            pos += 1 + max(1, int(g.lognormal(mix["doc_len_lognormal_mu"],
                                              mix["doc_len_lognormal_sigma"])))
        rows[b] = toks
    labels = rows[:, 1:].copy()
    labels[labels == eos] = -100
    return {"tokens": rows[:, :-1].copy(), "labels": labels}


def train_pool(mix: dict, vocab: int, seed: int) -> list[dict]:
    zipf = Zipf(seed, vocab, mix["zipf_s"])
    return [train_batch(mix, vocab, seed, i, zipf)
            for i in range(mix["pool_batches"])]


def serve_prompt_len(mix: dict, index: int) -> int:
    deck = [L for L, n in zip(mix["prompt_lens"], mix["deck"])
            for _ in range(n)]
    return deck[index % len(deck)]


def serve_batch(mix: dict, vocab: int, seed: int, index: int,
                zipf: Zipf | None = None) -> np.ndarray:
    """Prompts of batch ``index``: int32 (batch, prompt_len)."""
    zipf = zipf or Zipf(seed, vocab, mix["zipf_s"])
    P = serve_prompt_len(mix, index)
    return zipf.draw(rng(seed, 3, index), (mix["batch"], P))
