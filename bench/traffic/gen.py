"""The one traffic generator: an FCPR epoch of token batches from a mix file.

A mix (``bench/traffic/<name>.json``) fixes the epoch's shape: ``n_batches``
batches of ``batch_per_chip`` x chips rows of ``seq_len`` tokens, of which
``hard_batches`` are uniform random tokens and the rest come from a Markov
stream with learnable structure.  Everything is drawn from the run's seed;
every seed gets the same sizes and the same count of hard batches, at
positions drawn from the seed.

The Markov stream is a copy of ``repro.data.synthetic.make_lm_tokens`` (kept
here so that the yardstick does not move with the program).
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int) -> list[int]:
    """``n`` independent 31-bit seeds from one seed of any size."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, dtype=np.uint32)
    return [int(w) & 0x7FFFFFFF for w in state]


def markov_tokens(rng: np.random.RandomState, n_seqs: int, seq_len: int,
                  vocab: int, n_ctx: int, branch: int, p_follow: float):
    """Order-2-like Markov stream: each context maps to ``branch`` likely
    next tokens, taken with probability ``p_follow``."""
    table = rng.randint(0, vocab, size=(n_ctx, branch))
    toks = rng.randint(0, vocab, size=(n_seqs, seq_len))
    ctx = rng.randint(0, n_ctx, size=n_seqs)
    for t in range(1, seq_len):
        choice = table[ctx, rng.randint(0, branch, size=n_seqs)]
        mask = rng.rand(n_seqs) < p_follow
        toks[:, t] = np.where(mask, choice, toks[:, t])
        ctx = (ctx * 31 + toks[:, t]) % n_ctx
    return toks.astype(np.int32)


def make_epoch(mix: dict, seed: int, vocab: int, chips: int):
    """-> (tokens (n_batches * global_batch, seq_len) int32, hard batch
    indices).  Batch t is rows ``[t * global_batch, (t + 1) * global_batch)``."""
    n_b, S = mix["n_batches"], mix["seq_len"]
    gb = mix["batch_per_chip"] * chips
    n_hard = mix["hard_batches"]
    s_order, s_markov, s_hard = seed_words(seed, 3)
    hard = np.sort(np.random.RandomState(s_order).permutation(n_b)[:n_hard])
    m = mix["markov"]
    easy = markov_tokens(np.random.RandomState(s_markov), (n_b - n_hard) * gb,
                         S, vocab, m["n_ctx"], m["branch"], m["p_follow"])
    noise = np.random.RandomState(s_hard).randint(
        0, vocab, size=(n_hard * gb, S)).astype(np.int32)
    out = np.empty((n_b * gb, S), np.int32)
    ei = hi = 0
    for t in range(n_b):
        rows = slice(t * gb, (t + 1) * gb)
        if t in hard:
            out[rows] = noise[hi * gb:(hi + 1) * gb]
            hi += 1
        else:
            out[rows] = easy[ei * gb:(ei + 1) * gb]
            ei += 1
    return out, [int(t) for t in hard]
