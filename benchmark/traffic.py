"""The one traffic generator: a mix is a data file of parameters
(``traffic/<mix>.json``), a schedule is what this module draws from it and a
seed.

Stratified draws. A length comes from the inverse CDF of its distribution on a
jittered quantile grid ``u_i = (i + U_i) / N`` that the seed permutes, and so
do the arrival gaps, which are then rescaled so that exactly
``N = round(rate * seconds)`` requests are due inside the window. The
distributions are the ones the file states; every seed offers the same tokens
to within about a percent, in another order (independent draws gave +-6% in
offered output tokens over ~170 requests, PR 23).

With ``"order": "rotate"`` a mix goes one step further, as the contract
advises where runs on different seeds differ far more than two runs of one
seed: the lengths and gaps are drawn once, from the mix's ``base_seed``, laid
on a circle as long as the window, and ``--seed`` chooses where on the circle
the window opens (and every prompt's words). Every seed then offers the very
same requests with the same neighbours, in an order rotated by the seed; the
ramp is the end of the circle, served before the window opens.

A schedule has three phases: ``ramp`` (requests due before the window opens,
served and not counted), ``window`` (the requests that are judged, each timed
from when it was due) and nothing after it: the window's requests drain.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from benchmark import tokenizer as toktext

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


@dataclass
class Request:
    rid: int
    due_s: float  # relative to the window's opening; negative = ramp
    prompt_tokens: int  # tokens the engine sees, chat template included
    max_tokens: int
    counted: bool
    word_ids: List[int]  # the user message's vocabulary ids


def _quantile_grid(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` points, one in each of ``n`` equal slices of (0, 1), shuffled."""
    u = (np.arange(n) + rng.random(n)) / n
    rng.shuffle(u)
    return np.clip(u, 1e-9, 1 - 1e-9)


def inverse_cdf(spec: dict, u: np.ndarray) -> np.ndarray:
    """Values of the distribution ``spec`` at quantiles ``u``."""
    from scipy import stats

    dist = spec["dist"]
    if dist == "constant":
        x = np.full_like(u, float(spec["value"]))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * stats.norm.ppf(u))
    elif dist == "gamma":  # mean 1, coefficient of variation cv
        shape = 1.0 / (spec["cv"] ** 2)
        x = stats.gamma.ppf(u, a=shape, scale=1.0 / shape)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec and dist != "uniform":
        x = np.maximum(x, spec["min"])
    if "max" in spec and dist != "uniform":
        x = np.minimum(x, spec["max"])
    return x


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    return np.rint(inverse_cdf(spec, _quantile_grid(rng, n))).astype(np.int64)


def _due_times(rng, spec: dict, n: int, span_s: float) -> np.ndarray:
    """``n`` due times inside ``(0, span_s)``: stratified gaps, rescaled."""
    gaps = inverse_cdf(spec, _quantile_grid(rng, n))
    t = np.cumsum(gaps)
    return t * (span_s * (1.0 - 0.5 / n) / t[-1])


def _phase(rng, mix: dict, n: int, start_s: float, span_s: float, vocab_size: int,
           counted: bool, first_rid: int) -> List[Request]:
    if n <= 0:
        return []
    prompts = _lengths(rng, mix["prompt_tokens"], n)
    outputs = _lengths(rng, mix["output_tokens"], n)
    due = start_s + _due_times(rng, mix["arrival"], n, span_s)
    out = []
    for i in range(n):
        words = _words(rng, prompts[i], vocab_size)
        out.append(Request(first_rid + i, float(due[i]), len(words) + toktext.CHAT_OVERHEAD_TOKENS,
                           int(outputs[i]), counted, words))
    return out


def _words(rng, n_prompt_tokens: int, vocab_size: int) -> List[int]:
    n_words = max(1, int(n_prompt_tokens) - toktext.CHAT_OVERHEAD_TOKENS)
    return rng.integers(toktext.first_plain_id(), vocab_size, size=n_words).tolist()


def _rotated(mix: dict, rng, rate: float, seconds: float, ramp_s: float, vocab_size: int) -> List[Request]:
    """The mix's one base schedule on a circle of ``seconds``, opened where the seed says."""
    n = max(1, round(rate * seconds))
    base = np.random.default_rng([int(mix["base_seed"]), n])
    prompts = _lengths(base, mix["prompt_tokens"], n)
    outputs = _lengths(base, mix["output_tokens"], n)
    due = _due_times(base, mix["arrival"], n, float(seconds))
    k = int(rng.integers(n))
    cut = 0.5 * (due[k] + (due[k - 1] if k else due[-1] - seconds))  # mid-gap before request k
    order = np.roll(np.arange(n), -k)
    at = np.mod(due[order] - cut, seconds)
    ramp = [i for i in range(n) if at[i] >= seconds - ramp_s]  # the end of the circle, one turn earlier
    reqs = []
    for i in ramp + list(range(n)):
        counted = len(reqs) >= len(ramp)
        reqs.append(Request(len(reqs), float(at[i] - (0.0 if counted else seconds)), 0, int(outputs[order[i]]),
                            counted, _words(rng, prompts[order[i]], vocab_size)))
        reqs[-1].prompt_tokens = len(reqs[-1].word_ids) + toktext.CHAT_OVERHEAD_TOKENS
    return reqs


def schedule(mix: dict, seed: int, seconds: float, vocab_size: int, *, ramp: bool = True,
             rate_scale: float = 1.0) -> List[Request]:
    """The requests of one run: a ramp of ``mix['ramp_s']`` seconds before the
    window (if ``ramp``) and ``round(rate * seconds)`` requests inside it."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 24])
    rate = float(mix["rate_rps"]) * rate_scale
    ramp_s = float(mix.get("ramp_s", 0.0)) if ramp else 0.0
    if mix.get("order", "permute") == "rotate":
        return _rotated(mix, rng, rate, float(seconds), ramp_s, vocab_size)
    n = max(1, round(rate * seconds))
    reqs: List[Request] = []
    n_ramp = round(rate * ramp_s)
    reqs += _phase(rng, mix, n_ramp, -ramp_s, ramp_s, vocab_size, False, 0)
    reqs += _phase(rng, mix, n, 0.0, float(seconds), vocab_size, True, len(reqs))
    return reqs


def body(req: Request, model: str, mix: dict) -> dict:
    """The OpenAI chat request for ``req``."""
    return {
        "model": model,
        "messages": [{"role": "user", "content": " ".join(toktext.word(i) for i in req.word_ids)}],
        "max_tokens": req.max_tokens,
        "temperature": float(mix.get("temperature", 0.0)),
        "stream": bool(mix.get("stream", True)),
        "nvext": {"ignore_eos": bool(mix.get("ignore_eos", True))},
    }


def write_schedule(path: str, reqs: List[Request], model: str, mix: dict) -> None:
    """One JSON line per request for the load generator's child process."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        for r in reqs:
            f.write(json.dumps({"rid": r.rid, "due_s": r.due_s, "max_tokens": r.max_tokens,
                                "prompt_tokens": r.prompt_tokens, "counted": r.counted,
                                "body": body(r, model, mix)}) + "\n")
    os.replace(tmp, path)


def offered(reqs: List[Request]) -> Dict[str, float]:
    c = [r for r in reqs if r.counted]
    return {"requests": len(c), "prompt_tokens": sum(r.prompt_tokens for r in c),
            "output_tokens": sum(r.max_tokens for r in c)}
