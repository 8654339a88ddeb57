"""Requests due on a schedule whatever the server does: Poisson arrivals at
``rate_per_s``, prompt and output lengths lognormal(``median``, ``sigma``)
clipped to [``lo``, ``hi``], token ids uniform over the vocabulary, greedy.

Gaps and lengths are the quantiles of their distribution at (i + 0.5) / n,
each shuffled by the seed on its own: the same multiset for every seed."""
import math
import statistics

import numpy as np

from chipbench import loadgen

_NORMAL = statistics.NormalDist()


def lognormal_lengths(spec, n):
    """``n`` whole lengths: the quantiles of lognormal(median, sigma) at
    (i + 0.5) / n, clipped to [lo, hi]."""
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + sigma * z)))
        out.append(min(max(v, int(spec["lo"])), int(spec["hi"])))
    return out


def generate(mix, seed, seconds, vocab):
    """A list of ``{"due_s", "prompt", "max_new_tokens"}`` sorted by due
    time, all due inside ``seconds``; the gaps are rescaled so that the n-th
    request is due at n / rate."""
    rate = float(mix["rate_per_s"])
    n = max(int(rate * float(seconds)), 1)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps *= (n / rate) / gaps.sum() * (1.0 - 0.5 / n)
    rng = loadgen.rng(seed, 2)
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    prompts = np.array(lognormal_lengths(mix["prompt_tokens"], n))
    outputs = np.array(lognormal_lengths(mix["output_tokens"], n))
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    ids = loadgen.rng(seed, 3)
    return [{"due_s": float(due[i]),
             "prompt": ids.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new_tokens": int(outputs[i])} for i in range(n)]
