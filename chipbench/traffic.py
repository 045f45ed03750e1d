"""The one general traffic generator: balanced blocks from fixed grids.

A traffic mix is a data file under ``chipbench/traffic/`` (see any of
them for the keys). Requests are dealt in *balanced blocks*: one block
holds every pairing of the prompt-length grid and the output-length grid
exactly once, as *balanced rows* (a Latin arrangement): each row holds
every length of the longer grid once, paired with the lengths of the
shorter grid in rotation, so that the rows of a block together hold
every pairing once and every row costs the same.

The order of lengths is the same for every seed; ``--seed`` draws the
token ids (and the weights). ISSUE 23 let the seed order a block, and on
the chip that order alone moved a cell: with 44 long-prompt requests in
a window, what fell between the marks followed the order, and tokens/s
read 30.5 to 33.2 over six seeds while each seed repeated itself within
0.5% (my chip runs, PR 23). A seed that changes the work is noise that
no bound under 10% covers, so it does not.
"""
import itertools
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    """The traffic file `chipbench/traffic/<name>.json` as a dict."""
    path = os.path.join(HERE, "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if "driver" not in mix:
        raise ValueError(f"{path}: a traffic file names its driver")
    return mix


def seed_words(seed):
    """A --seed of any size (the driver's pass 2**31) as two uint32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF]


def block_rows(prompt_lens, output_lens):
    """One block's work as balanced rows of (prompt_len, max_new)."""
    swap = len(output_lens) > len(prompt_lens)
    major, minor = (output_lens, prompt_lens) if swap \
        else (prompt_lens, output_lens)
    rows = []
    for r in range(len(minor)):
        row = [(a, minor[(j + r) % len(minor)])
               for j, a in enumerate(major)]
        rows.append([(b, a) for a, b in row] if swap else row)
    return rows


def block_pairs(prompt_lens, output_lens):
    """Every pairing of the two grids, once: one block's work."""
    return [pair for row in block_rows(prompt_lens, output_lens)
            for pair in row]


def deal(prompt_lens, output_lens):
    """Endless (prompt_len, max_new) pairs in balanced blocks: the rows
    of a block, and the requests of a row, in an order drawn from the
    block's number alone."""
    rows = block_rows(prompt_lens, output_lens)
    for block in itertools.count():
        rng = np.random.default_rng([1, block])
        for r in rng.permutation(len(rows)):
            row = rows[int(r)]
            for i in rng.permutation(len(row)):
                yield row[int(i)]


def requests(mix, vocab_size, seed):
    """Endless (prompt ids, max_new) from a serving mix. Prompts are
    unshared random ids: no two share a page-aligned head but by
    chance, so the prefix cache never hits."""
    if mix.get("sharing", "none") != "none":
        raise ValueError(f"sharing {mix['sharing']!r}: this generator "
                         f"deals unshared prompts only")
    for n, (plen, max_new) in enumerate(
            deal(mix["prompt_lens"], mix["output_lens"])):
        rng = np.random.default_rng(seed_words(seed) + [2, n])
        yield rng.integers(0, vocab_size, plen, dtype=np.int64), int(max_new)


def train_batch(batch_size, seq_len, vocab_size, seed, step):
    """The seeded batch of one training step: (ids, labels) int32
    [B, T], labels the ids shifted left by one (next-token)."""
    rng = np.random.default_rng(seed_words(seed) + [3, int(step)])
    ids = rng.integers(0, vocab_size, (batch_size, seq_len), dtype=np.int32)
    return ids, np.roll(ids, -1, axis=1)


def longest_request(mix):
    return max(mix["prompt_lens"]) + max(mix["output_lens"])
