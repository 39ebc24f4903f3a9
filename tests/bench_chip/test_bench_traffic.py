"""The traffic generator: deterministic per seed, the same sizes for every
seed in another order, and warm-up prompts apart from the window's."""
from __future__ import annotations

import json

import numpy as np
import pytest

import bench_chip_util as u
import schedule

EVAL = json.loads((u.BENCH / "traffic" / "eval-batch.json").read_text())
SEEDS = (1, 2_718_281_829, 2**31 + 11, 2**32 + 5)


def lengths(batches):
    return [[len(q["prompt"]) for q in b] for b in batches]


@pytest.mark.parametrize("seed", SEEDS)
def test_eval_batches_are_deterministic_per_seed(seed):
    a = schedule.eval_batches(EVAL, 32064, seed, 2)
    b = schedule.eval_batches(EVAL, 32064, seed, 2)
    assert lengths(a) == lengths(b)
    for x, y in zip(a, b):
        for p, q in zip(x, y):
            np.testing.assert_array_equal(p["prompt"], q["prompt"])
            assert p["max_new_tokens"] == q["max_new_tokens"] == 128


def test_eval_batches_keep_each_rounds_lengths_and_vary_the_tokens():
    a = schedule.eval_batches(EVAL, 32064, 11, 2)
    b = schedule.eval_batches(EVAL, 32064, 12, 2)
    slots = EVAL["slots"]
    for x, y in ((a, b),):
        for bx, by in zip(x, y):
            assert len(bx) == EVAL["batch"]
            for r in range(EVAL["batch"] // slots):
                lx = sorted(len(q["prompt"]) for q in bx[r*slots:(r+1)*slots])
                ly = sorted(len(q["prompt"]) for q in by[r*slots:(r+1)*slots])
                assert lx == ly == sorted(EVAL["rounds"][r % 2])
    assert any((p["prompt"] != q["prompt"]).any() for p, q in
               zip(a[0], b[0]) if len(p["prompt"]) == len(q["prompt"]))
    warm = schedule.eval_batches(EVAL, 32064, 11, 1, "warm")
    assert all((w["prompt"][:8] != x["prompt"][:8]).any()
               for w, x in zip(warm[0], a[0])
               if len(w["prompt"]) == len(x["prompt"]))


def test_every_seed_gets_the_same_work():
    def work(seed):
        return sorted(sum(lengths(schedule.eval_batches(EVAL, 32064, seed,
                                                        3)), []))
    assert work(SEEDS[0]) == work(SEEDS[1]) == work(SEEDS[2])


def test_prompt_ids_lie_in_the_vocabulary():
    for b in schedule.eval_batches(EVAL, 32064, 2**31 + 3, 1):
        for q in b:
            assert q["prompt"].min() >= 0 and q["prompt"].max() < 32064
