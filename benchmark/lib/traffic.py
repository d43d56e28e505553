"""The one general traffic generator. A traffic mix is a data file under
`benchmark/traffic/`; its `kind` chooses the runner and everything else is
parameters read here. `--seed` decides order, token ids and arrival times,
never how much work is offered: every seed sends the same multiset.

kinds
  train         token batches {"tokens": (batch, seq+1) int32} for the
                trainer's feed; every row differs, and a model can learn
                them (next = (3*tok + noise) % vocab, as the program's own
                synthetic stream does), so the loss falls over a window.
  serve-open    requests with Poisson-like arrivals at `rate_rps`: the gaps
                of a period are the exponential quantiles, permuted by the
                seed (see arrival_gaps).

A serving file states one period of requests as rows
[prompt_tokens, output_tokens, count]. The generator repeats the period;
inside each period the seed shuffles the order.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# -- training ---------------------------------------------------------------

def train_batches(mix: dict, vocab_size: int, seed: int):
    """Endless batches of `batch_size` rows of `seq_len`+1 tokens."""
    b, s, v = int(mix["batch_size"]), int(mix["seq_len"]), int(vocab_size)
    rng = _rng(seed, 1)
    while True:
        toks = np.empty((b, s + 1), np.int64)
        toks[:, 0] = rng.integers(0, v, b)
        noise = rng.integers(0, 2, (b, s))
        # affine prefix scan of next = (3*tok + noise) % v, vectorised
        a = np.full((b, s), 3 % v, np.int64)
        acc = noise.astype(np.int64) % v
        shift = 1
        while shift < s:
            hi = a[:, shift:].copy()
            acc[:, shift:] = (hi * acc[:, :-shift] + acc[:, shift:]) % v
            a[:, shift:] = (hi * a[:, :-shift]) % v
            shift *= 2
        toks[:, 1:] = (a * toks[:, :1] + acc) % v
        yield {"tokens": toks.astype(np.int32)}


# -- serving ----------------------------------------------------------------

@dataclass
class Request:
    index: int
    prompt: list
    max_new_tokens: int
    due_s: float = 0.0          # offset from the start of the first period


def period_rows(mix: dict) -> list:
    """The multiset of one period, expanded: [(prompt_len, out_len), ...]
    in the file's order."""
    rows = []
    for p, o, n in mix["period"]:
        rows += [(int(p), int(o))] * int(n)
    return rows


def check_budget(mix: dict, token_budget: int) -> None:
    for p, o in period_rows(mix):
        if p + o > token_budget:
            raise ValueError(f"traffic {mix['name']}: request of {p}+{o} "
                             f"tokens exceeds the budget {token_budget}")


def period_seconds(mix: dict) -> float:
    """How long one period of the file's requests takes to arrive."""
    return len(period_rows(mix)) / float(mix["rate_rps"])


def request_stream(mix: dict, vocab_size: int, seed: int):
    """Endless requests: periods of the file's multiset, each shuffled by
    the seed; token ids from the seed; arrival gaps at `rate_rps`, one
    period's set of them permuted by the seed in every period. Period k's
    arrivals all fall inside [k, k+1) periods: the first is due half the
    smallest gap before its gap has passed, so the last, a whole period
    of gaps later, stays that far inside."""
    rows = period_rows(mix)
    order_rng, tok_rng, arr_rng = _rng(seed, 2), _rng(seed, 3), _rng(seed, 4)
    gaps = arrival_gaps(len(rows), float(mix["rate_rps"]))
    t, i = -float(gaps.min()) / 2, 0
    while True:
        gap_order = arr_rng.permutation(len(rows))
        for k, j in enumerate(order_rng.permutation(len(rows))):
            p, o = rows[j]
            t += float(gaps[gap_order[k]])
            yield Request(i, tok_rng.integers(0, vocab_size, p).tolist(),
                          o, t)
            i += 1


def arrival_gaps(n: int, rate_rps: float) -> np.ndarray:
    """The n gaps between the arrivals of one period: the exponential
    distribution's quantiles at (k + 0.5)/n, scaled so that the n arrivals
    take exactly n / rate seconds. The seed permutes them over the whole
    period, so arrivals clump and thin out as a Poisson stream's do
    (exponential gaps in random order) while every seed offers the same
    requests in every whole period."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate_rps) / gaps.sum()


def prompt_lengths(mix: dict) -> list:
    return sorted({p for p, _ in period_rows(mix)})
