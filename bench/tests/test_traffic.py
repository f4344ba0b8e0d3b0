"""Every seed sends the window the same work in another order."""
import os

import numpy as np

import common
import traffic


def _chat():
    return common.load_json(os.path.join(os.path.dirname(__file__), "data",
                                         "chat.json"))


def test_window_gets_the_same_sizes_and_count_for_every_seed():
    t = _chat()
    seen = []
    for seed in (1, 2, 2**31 + 5, 7 * 2**40):
        load = traffic.open_loop(t, 65024, 30.0, common.np_rng(seed, 2))
        w = load["in_window"]
        plens = sorted(len(p) for p, x in zip(load["prompts"], w) if x)
        seen.append((int(w.sum()), plens, sorted(load["max_new"][w])))
        due = load["due"][w]
        assert due.min() >= load["window"][0]
        assert due.max() < load["window"][1]
        assert np.all(np.diff(load["due"]) >= 0)
    assert all(s[0] == round(t["rate_per_s"] * 30) for s in seen)
    assert all(s[1:] == seen[0][1:] for s in seen)


def test_lengths_stay_on_the_grid():
    t = _chat()
    lens = traffic.lengths(t["prompt"], 100, t["block"],
                           np.random.default_rng(0))
    assert set(lens) <= set(traffic.distinct_prompt_lengths(t["prompt"]))
    assert traffic.distinct_prompt_lengths(t["prompt"]) == [
        128, 256, 384, 512, 640, 768]


def test_seed_picks_the_tokens():
    t = _chat()
    a = traffic.open_loop(t, 65024, 10.0, common.np_rng(3, 2))
    b = traffic.open_loop(t, 65024, 10.0, common.np_rng(3, 2))
    c = traffic.open_loop(t, 65024, 10.0, common.np_rng(4, 2))
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"],
                                                     b["prompts"]))
    assert not all(np.array_equal(x, y) for x, y in zip(a["prompts"],
                                                         c["prompts"]))
