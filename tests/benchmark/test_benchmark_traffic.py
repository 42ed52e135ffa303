"""The stratified generator: every seed offers the same work in another order."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import tokenizer as toktext  # noqa: E402
from benchmark import traffic  # noqa: E402

SEEDS = [0, 1, 7, 12345, 2**31 + 5, 2**32 + 99]


@pytest.mark.parametrize("mix_name", ["chat", "chat-sat"])
def test_every_seed_offers_the_same_tokens_within_one_percent(mix_name):
    mix = traffic.load_mix(mix_name)
    totals = [traffic.offered(traffic.schedule(mix, s, 45, 32768)) for s in SEEDS]
    for key in ("prompt_tokens", "output_tokens"):
        v = [t[key] for t in totals]
        assert (max(v) - min(v)) / np.mean(v) < 0.01, (key, v)
    assert len({t["requests"] for t in totals}) == 1


@pytest.mark.parametrize("seconds", [10, 45, 51])
def test_exactly_rate_times_seconds_requests_are_due_inside_the_window(seconds):
    mix = traffic.load_mix("chat")
    for seed in SEEDS:
        reqs = traffic.schedule(mix, seed, seconds, 32768)
        counted = [r for r in reqs if r.counted]
        assert len(counted) == round(mix["rate_rps"] * seconds)
        assert all(0 < r.due_s < seconds for r in counted)
        ramp = [r for r in reqs if not r.counted]
        if mix.get("order") != "rotate":  # a rotated ramp is the end of the circle, however many are due there
            assert len(ramp) == round(mix["rate_rps"] * mix["ramp_s"])
        else:  # and those are the window's last requests, one turn earlier
            last = [r for r in counted if r.due_s >= seconds - mix["ramp_s"]]
            assert [(r.prompt_tokens, r.max_tokens) for r in ramp] == [(r.prompt_tokens, r.max_tokens) for r in last]
            assert all(abs(a.due_s + seconds - b.due_s) < 1e-9 for a, b in zip(ramp, last))
        assert ramp and all(-mix["ramp_s"] <= r.due_s < 0 for r in ramp)
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
        assert [r.rid for r in reqs] == list(range(len(reqs)))


def test_seeds_differ_in_order_and_same_seed_repeats():
    mix = traffic.load_mix("chat")
    a, b, a2 = (traffic.schedule(mix, s, 45, 32768) for s in (3, 4, 3))
    assert [r.max_tokens for r in a] != [r.max_tokens for r in b]
    assert [(r.due_s, r.max_tokens, r.word_ids) for r in a] == [(r.due_s, r.max_tokens, r.word_ids) for r in a2]


@pytest.mark.parametrize("mix_name", ["chat", "chat-sat"])
def test_a_rotated_mix_offers_every_seed_the_same_requests_with_the_same_neighbours(mix_name):
    mix = traffic.load_mix(mix_name)
    assert mix["order"] == "rotate"
    runs = [[r for r in traffic.schedule(mix, s, 50, 32768) if r.counted] for s in SEEDS]
    base = [(r.prompt_tokens, r.max_tokens) for r in runs[0]]
    starts = set()
    for reqs in runs:
        seq = [(r.prompt_tokens, r.max_tokens) for r in reqs]
        k = next(k for k in range(len(base)) if base[k:] + base[:k] == seq)  # a rotation of the same circle
        starts.add(k)
        gaps, base_gaps = np.diff([r.due_s for r in reqs]), np.diff([r.due_s for r in runs[0]])
        assert np.allclose(gaps[: len(gaps) - k], base_gaps[k:])  # the same gaps between the same neighbours
    assert len(starts) > 1  # the seeds open the window at different places
    assert len({tuple(reqs[0].word_ids[:8]) for reqs in runs}) == len(runs)  # the prompts' words are the seed's own


def test_lengths_keep_the_stated_distribution_and_its_clips():
    mix = traffic.load_mix("chat")
    reqs = [r for r in traffic.schedule(mix, 11, 200, 32768) if r.counted]
    prompts = np.array([r.prompt_tokens for r in reqs])
    outs = np.array([r.max_tokens for r in reqs])
    assert prompts.min() >= mix["prompt_tokens"]["min"] and prompts.max() <= mix["prompt_tokens"]["max"]
    assert outs.min() >= mix["output_tokens"]["min"] and outs.max() <= mix["output_tokens"]["max"]
    assert abs(np.median(prompts) / mix["prompt_tokens"]["median"] - 1) < 0.03
    assert abs(np.median(outs) / mix["output_tokens"]["median"] - 1) < 0.03


def test_a_prompt_is_exactly_its_stated_number_of_tokens():
    mix = traffic.load_mix("chat")
    tok = toktext.WordTokenizer(32768)
    for r in traffic.schedule(mix, 5, 10, 32768)[:20]:
        text = traffic.body(r, "m", mix)["messages"][0]["content"]
        rendered = f"<|user|>\n{text}\n<|assistant|>\n"  # the program's default chat template
        assert len(tok.encode(rendered)) == r.prompt_tokens
        assert all(i >= toktext.first_plain_id() for i in r.word_ids)


@pytest.mark.parametrize("spec", [
    {"dist": "gamma", "cv": 1.0}, {"dist": "gamma", "cv": 3.0},
    {"dist": "lognormal", "median": 10.0, "sigma": 0.5}, {"dist": "uniform", "min": 2.0, "max": 4.0},
    {"dist": "constant", "value": 3.0},
])
def test_inverse_cdfs_are_monotone_and_have_the_stated_centre(spec):
    u = np.linspace(0.001, 0.999, 999)
    x = traffic.inverse_cdf(spec, u)
    assert np.all(np.diff(x) >= 0)
    if spec["dist"] == "gamma":
        assert abs(x.mean() - 1.0) < 0.1  # mean 1 by construction
    if spec["dist"] == "lognormal":
        assert abs(np.median(x) - spec["median"]) < 0.05
