"""Balanced-block dealing: every seed offers the same work, in the
same order; the seed draws the token ids."""
import collections
import itertools
import json
import os

import numpy as np
import pytest

from chipbench import traffic

SEEDS = [0, 1, 12345, 2 ** 31 + 11, 3000000001]
MIXES = ["chat-closed-1x-slots", "decode-closed-1x-slots",
         "prompts-closed-1x-slots"]


@pytest.mark.parametrize("name", MIXES)
def test_each_block_holds_every_pairing_once(name):
    mix = traffic.load(name)
    pairs = sorted(traffic.block_pairs(mix["prompt_lens"],
                                       mix["output_lens"]))
    assert pairs == sorted(itertools.product(mix["prompt_lens"],
                                             mix["output_lens"]))
    dealt = list(itertools.islice(
        traffic.deal(mix["prompt_lens"], mix["output_lens"]),
        3 * len(pairs)))
    blocks = [dealt[b * len(pairs):(b + 1) * len(pairs)] for b in range(3)]
    assert all(sorted(block) == pairs for block in blocks)
    assert blocks[0] != blocks[1], "every block in one order"


@pytest.mark.parametrize("name", MIXES)
def test_the_seed_draws_the_ids_and_never_the_lengths(name):
    mix = traffic.load(name)
    seen = []
    for seed in SEEDS:
        reqs = list(itertools.islice(traffic.requests(mix, 50304, seed), 30))
        seen.append(([(len(p), n) for p, n in reqs],
                     tuple(int(p[0]) for p, _ in reqs)))
    assert all(lens == seen[0][0] for lens, _ in seen)
    assert len({ids for _, ids in seen}) == len(SEEDS)


@pytest.mark.parametrize("name", MIXES)
def test_each_row_holds_the_longer_grid_once_and_the_shorter_in_turn(name):
    mix = traffic.load(name)
    P, O = mix["prompt_lens"], mix["output_lens"]
    rows = traffic.block_rows(P, O)
    n, m = max(len(P), len(O)), min(len(P), len(O))
    assert len(rows) == m and all(len(r) == n for r in rows)
    for row in rows:
        major = [p if len(P) >= len(O) else o for p, o in row]
        minor = collections.Counter(o if len(P) >= len(O) else p
                                    for p, o in row)
        assert sorted(major) == sorted(P if len(P) >= len(O) else O)
        assert set(minor.values()) <= {n // m, -(-n // m)}
    # the dealt stream is whole rows
    dealt = list(itertools.islice(traffic.deal(P, O), 4 * n))
    for k in range(4):
        assert sorted(dealt[k * n:(k + 1) * n]) in [sorted(r) for r in rows]


@pytest.mark.parametrize("name", MIXES)
def test_requests_carry_the_dealt_lengths_and_valid_ids(name):
    mix = traffic.load(name)
    seed = 2 ** 31 + 5
    lens = list(itertools.islice(
        traffic.deal(mix["prompt_lens"], mix["output_lens"]), 12))
    reqs = list(itertools.islice(traffic.requests(mix, 50304, seed), 12))
    assert [(len(p), n) for p, n in reqs] == lens
    assert all(0 <= p.min() and p.max() < 50304 for p, _ in reqs)
    again = list(itertools.islice(traffic.requests(mix, 50304, seed), 12))
    assert all((a[0] == b[0]).all() for a, b in zip(reqs, again))
    heads = collections.Counter(tuple(p[:16]) for p, _ in reqs)
    assert max(heads.values()) == 1, "prompts share a page-aligned head"


def test_prompts_grid_sits_inside_one_mode():
    """20% / 60% / 20% of requests on the 512 / 1,024 / 2,048 prefill
    rungs: the median and the stalls' 64th percentile sit in the
    middle mode."""
    mix = traffic.load("prompts-closed-1x-slots")
    rung = lambda n: 1 << (n - 1).bit_length()
    share = collections.Counter(rung(p) for p in mix["prompt_lens"])
    n = len(mix["prompt_lens"])
    assert {k: v / n for k, v in share.items()} == \
        {512: 0.2, 1024: 0.6, 2048: 0.2}
    assert traffic.longest_request(mix) == 1584 < 2048


def test_seed_words_hold_more_than_32_bits():
    assert traffic.seed_words(2 ** 31 + 1) == [0, 2 ** 31 + 1]
    assert traffic.seed_words(2 ** 32 + 3) == [1, 3]
    with pytest.raises(ValueError):
        traffic.seed_words(-1)


def test_train_batches_are_seeded_and_shifted():
    a = traffic.train_batch(2, 16, 512, 2 ** 31 + 9, 4)
    b = traffic.train_batch(2, 16, 512, 2 ** 31 + 9, 4)
    c = traffic.train_batch(2, 16, 512, 2 ** 31 + 9, 5)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[0] != c[0]).any()
    assert (a[1][:, :-1] == a[0][:, 1:]).all()
    assert a[0].dtype == np.int32 and a[0].shape == (2, 16)


def test_a_mix_with_sharing_needs_another_generator():
    mix = dict(traffic.load("chat-closed-1x-slots"), sharing="prefix")
    with pytest.raises(ValueError):
        next(traffic.requests(mix, 512, 0))


def test_traffic_files_are_data():
    d = os.path.join(os.path.dirname(traffic.__file__), "traffic")
    for f in os.listdir(d):
        assert f.endswith(".json")
        with open(os.path.join(d, f)) as fh:
            assert json.load(fh)["driver"] in ("train", "serve")
