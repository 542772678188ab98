"""Open-loop serving traffic: requests fall due on a schedule whatever the
server does.

The mix is a data file's ``traffic`` block: ``rate_per_s`` and log-normal
prompt and output lengths (median, sigma, clip). A window of ``seconds``
holds n = rate x seconds requests. Every seed carries the same SET of n
prompt lengths, n output lengths and n gaps between arrivals (the
distributions' quantiles at (i + 1/2)/n: exponential gaps, so Poisson
arrivals), each in an order of its own drawn from ``--seed``, and prompts
whose tokens are drawn from ``--seed``: the same work in every run,
arranged differently. Two independent draws of some tens of requests
differ by more than a change to the system would move them (PR 23: the
first-token median read 229 to 258 ms over six schedules of one mix).
"""

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    due: float          # seconds after the window opens
    prompt: list
    max_new_tokens: int


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def _lognormal(n, spec):
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def requests(seed, traffic, size, seconds):
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    rng = np.random.default_rng([seed % 2 ** 31, seed // 2 ** 31, 3])
    prompt_lens = rng.permutation(_lognormal(n, traffic["prompt_tokens"]))
    output_lens = rng.permutation(_lognormal(n, traffic["output_tokens"]))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))   # Poisson arrivals
    gaps *= seconds * n / (n + 1) / gaps.sum()
    due = np.cumsum(gaps)
    out = []
    for t, n_prompt, n_out in zip(due, prompt_lens, output_lens):
        prompt = rng.integers(0, size["vocab_size"], n_prompt)
        out.append(Request(float(t), [int(x) for x in prompt], int(n_out)))
    return out
