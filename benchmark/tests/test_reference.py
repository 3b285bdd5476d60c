"""The plain reference against numpy, and its control: the reference one
precision down, put in the program's place, has to come out not
correct by the limits each configuration states. Each configuration's
file names its own control (its ``control`` key: the reference's
precision that has to pass and the one below it that has to fail, on
how many users), at the size of its ``rehearsal`` with the control's own
keys laid over it."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import factors
from benchmark.reference import als_topk

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def controlled(name: str) -> dict:
    """The configuration at its control's size, with its ``control``."""
    with open(os.path.join(CONFIGS, name)) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearsal"]["config"], **cfg["control"]["config"]}


def limits():
    out = {}
    for name in sorted(os.listdir(CONFIGS)):
        with open(os.path.join(CONFIGS, name)) as f:
            out[name] = json.load(f)["limits"]
    return out


TINY = controlled("als-msd-d2048.json")


def test_rows_are_the_same_alone_and_in_the_table():
    whole = np.asarray(factors.make_table(2**31 + 5, "user", 40, 64, 8, 0.1))
    some = np.asarray(factors.make_rows(2**31 + 5, "user", [3, 17, 39], 64,
                                        8, 0.1))
    assert np.array_equal(whole[[3, 17, 39]], some)
    other = np.asarray(factors.make_table(2**31 + 6, "user", 40, 64, 8, 0.1))
    assert not np.array_equal(whole, other)
    assert whole[:, :8].std() > 5 * whole[:, 8:].std()


def test_reference_agrees_with_float64_numpy():
    seed, rows, k = 77, np.arange(0, 900, 37), 10
    top_s, top_i = als_topk.top_k(TINY, seed, rows, k)
    p = TINY["planted"]
    u = np.asarray(factors.make_rows(seed, "user", rows, 2048, p["rank"],
                                     p["noise"]), np.float64)
    v = np.asarray(factors.make_table(seed, "item", 5000, 2048, p["rank"],
                                      p["noise"]), np.float64)
    scores = u @ v.T
    want = np.argsort(-scores, axis=1)[:, :k]
    assert np.array_equal(top_i, want)
    assert np.allclose(top_s, np.take_along_axis(scores, want, 1),
                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 123456789])
@pytest.mark.parametrize("name,lim", sorted(limits().items()))
def test_control_one_precision_down_is_not_correct(name, lim, seed):
    cfg = controlled(name)
    ctl = cfg["control"]
    reference = importlib.import_module(
        "benchmark.reference." + cfg["reference"])
    rows = np.random.default_rng(seed).choice(cfg["n_users"], ctl["users"],
                                              replace=False)
    sound = reference.control(cfg, seed, rows, 10, ctl["sound"])
    # at the sound precision the control is the reference itself
    assert sound["score_err"] == 0.0 and sound["rank_gap"] == 0.0, sound
    assert reference.judge(sound, lim), sound
    low = reference.control(cfg, seed, rows, 10, ctl["low"])
    assert not reference.judge(low, lim), low


def test_malformed_answers_are_counted():
    rows = np.array([1, 2, 3])
    top_s, top_i = als_topk.top_k(TINY, 5, rows, 10)
    answers = [(i.tolist(), s.tolist()) for s, i in zip(top_s, top_i)]
    answers[1] = (answers[1][0][:9], answers[1][1][:9])      # too few
    answers[2] = None                                        # unparsable
    numbers = als_topk.compare(TINY, 5, rows, answers, 10)
    assert numbers["malformed"] == 2 and numbers["compared"] == 1
    assert not als_topk.judge(numbers, {"score_err": 1, "rank_gap": 1})
    assert als_topk.parse_answer(b"not json") is None
    body = json.dumps({"itemScores": [{"item": "i7", "score": 1.5}]})
    assert als_topk.parse_answer(body.encode()) == ([7], [1.5])
