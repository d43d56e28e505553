import collections
import itertools
import json
import os

import numpy as np
import pytest
from conftest import BENCH

from lib import traffic

SERVING = ("chat-open-2rps",)
SEEDS = (3, 4100000011)


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return dict(json.load(f), name=name)


def _lengths(mix, seed, n):
    return collections.Counter(
        (len(r.prompt), r.max_new_tokens) for r in itertools.islice(
            traffic.request_stream(mix, 32000, seed), n))


@pytest.mark.parametrize("name", SERVING)
def test_two_seeds_offer_the_same_multiset(name):
    mix = _mix(name)
    n = len(traffic.period_rows(mix))
    assert n == mix["period_requests"] == 50
    want = collections.Counter(traffic.period_rows(mix))
    for periods in (1, 3):
        a, b = (_lengths(mix, s, periods * n) for s in SEEDS)
        assert a == b == collections.Counter(
            {k: v * periods for k, v in want.items()})
    # but not in the same order, nor with the same token ids
    ra, rb = (list(itertools.islice(traffic.request_stream(mix, 32000, s), n))
              for s in SEEDS)
    assert [len(r.prompt) for r in ra] != [len(r.prompt) for r in rb]
    assert ra[0].prompt[:8] != rb[0].prompt[:8]


@pytest.mark.parametrize("name", SERVING)
def test_the_file_states_its_totals_and_never_exceeds_the_budget(name):
    mix = _mix(name)
    rows = traffic.period_rows(mix)
    assert sum(p for p, _ in rows) == mix["period_prompt_tokens"]
    assert sum(o for _, o in rows) == mix["period_output_tokens"]
    with open(os.path.join(BENCH, "configs", "mistral-7b-serve.json")) as f:
        budget = json.load(f)["run"]["token_budget"]
    traffic.check_budget(mix, budget)
    assert max(p + o for p, o in rows) <= budget
    with pytest.raises(ValueError):
        traffic.check_budget(mix, 1000)
    # only the prompt lengths that are warmed up
    assert traffic.prompt_lengths(mix) == [64, 128, 256, 384, 512, 768,
                                           1024, 1536]


def test_open_loop_arrivals_are_one_set_of_gaps_in_another_order():
    mix = _mix("chat-open-2rps")
    n, rate = mix["period_requests"], mix["rate_rps"]
    period_s = traffic.period_seconds(mix)
    assert period_s == n / rate == 25.0
    want = np.sort(traffic.arrival_gaps(n, rate))
    assert want.sum() == pytest.approx(period_s)
    dues = []
    for seed in SEEDS + (17, 2999999999):
        reqs = list(itertools.islice(
            traffic.request_stream(mix, 32000, seed), 4 * n))
        due = np.array([r.due_s for r in reqs])
        dues.append(due)
        assert (np.diff(due) > 0).all() and due[0] > 0
        # every period is the same set of gaps in an order the seed draws
        for k in (1, 2):
            gaps = np.sort(np.diff(due[k * n - 1:(k + 1) * n]))
            assert gaps == pytest.approx(want)
        # so period k's requests, and no others, are due in [k, k+1)
        # periods: a ramp of one period and a 50 s window hold periods 1, 2
        for k in range(4):
            inside = [r for r in reqs
                      if k * period_s <= r.due_s < (k + 1) * period_s]
            assert [r.index for r in inside] == list(range(k * n, (k + 1) * n))
            assert collections.Counter(
                (len(r.prompt), r.max_new_tokens) for r in inside) == \
                collections.Counter(traffic.period_rows(mix))
    assert not np.allclose(dues[0], dues[1])
    # the gaps are spread over the whole period, so arrivals clump: some
    # 5 s of a period hold well over, some well under, the mean of 10
    counts = [int(((due >= a) & (due < a + 5.0)).sum())
              for due in dues for a in np.arange(0, 100, 5.0)]
    assert min(counts) <= 6 and max(counts) >= 14


def test_train_rows_all_differ_and_follow_the_seed():
    mix = {"batch_size": 4, "seq_len": 128}
    a = list(itertools.islice(traffic.train_batches(mix, 32000, 9), 3))
    b = list(itertools.islice(traffic.train_batches(mix, 32000, 9), 3))
    c = next(traffic.train_batches(mix, 32000, 2200000011))
    rows = np.concatenate([x["tokens"] for x in a])
    assert rows.shape == (12, 129) and rows.dtype == np.int32
    assert len({r.tobytes() for r in rows}) == 12
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert not (a[0]["tokens"] == c["tokens"]).all()
    # learnable: next = (3 * tok + noise) % vocab with noise in {0, 1}
    t = rows.astype(np.int64)
    assert set(np.unique((t[:, 1:] - 3 * t[:, :-1]) % 32000)) <= {0, 1}
    assert rows.min() >= 0 and rows.max() < 32000
