"""The one generator of serving traffic. A mix is a dict of parameters in
the cell file (``traffic``); the program receives only the requests.

Every seed gets the same work: the sizes, the arrival times and which size
is due when are drawn once from the mix's own ``size_seed``, and ``--seed``
draws only the token ids. So seeds differ in content, never in the schedule
(the order of sizes alone moves a p95 over a window by several per cent).

Parameters (all but ``rate_per_s`` optional):

* ``rate_per_s`` — mean arrival rate; ``round(rate * seconds)`` requests are
  due inside the window.
* ``arrivals`` — ``"poisson"`` (default) or ``"on_off"``: Poisson arrivals
  only while a source is on, with ``on_s`` / ``off_s`` periods, at the rate
  that offers ``rate_per_s`` on average.
* ``prompt`` / ``output`` — ``{"median", "sigma", "min", "max"}``: lognormal
  token counts, clipped.
* ``max_total`` — prompt + output never exceeds it (output is cut).
* ``shared_prefix`` — ``{"count", "tokens", "zipf_a"}``: each prompt starts
  with one of ``count`` system prompts of ``tokens`` tokens, picked by a
  Zipf law; ``prompt`` then sizes the user turn that follows.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec.get("sigma", 0.5), n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _arrivals(rng, mix: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` due times spread over ``(0, seconds)``: exponential gaps scaled
    so that the last request is due just inside the window."""
    gaps = rng.exponential(1.0, n + 1)
    if mix.get("arrivals", "poisson") == "on_off":
        on, off = float(mix["on_s"]), float(mix["off_s"])
        # due times in "on" time, then stretched over the off periods
        busy = np.cumsum(gaps)[:n] / gaps.sum() * seconds * on / (on + off)
        periods = np.floor(busy / on)
        return busy + periods * off
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> list[Request]:
    """The requests of one run, sorted by due time."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    base = np.random.default_rng(int(mix.get("size_seed", 0)))
    prompt_len = _lognormal(base, mix["prompt"], n)
    out_len = _lognormal(base, mix["output"], n)
    due = _arrivals(base, mix, n, seconds)
    shared = mix.get("shared_prefix")
    prefix_of = None
    if shared:
        ranks = np.arange(1, shared["count"] + 1, dtype=np.float64)
        p = ranks ** -float(shared.get("zipf_a", 1.0))
        prefix_of = base.choice(shared["count"], n, p=p / p.sum())
        prompt_len = prompt_len + shared["tokens"]
    if "max_total" in mix:
        out_len = np.minimum(out_len, mix["max_total"] - prompt_len)
    if (out_len < 1).any():
        raise ValueError("traffic mix leaves a request no output token")

    rng = np.random.default_rng(seed)
    prefixes = []
    if shared:
        prefixes = [rng.integers(0, vocab, shared["tokens"], dtype=np.int32)
                    for _ in range(shared["count"])]
    reqs = []
    for k in range(n):
        if shared:
            user = rng.integers(0, vocab, prompt_len[k] - shared["tokens"],
                                dtype=np.int32)
            prompt = np.concatenate([prefixes[prefix_of[k]], user])
        else:
            prompt = rng.integers(0, vocab, prompt_len[k], dtype=np.int32)
        reqs.append(Request(float(due[k]), prompt, int(out_len[k])))
    return reqs
