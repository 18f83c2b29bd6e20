"""The one load generator: an open-loop schedule from a seed and a
traffic file's parameters.

Everything is drawn from ``--seed``: Poisson arrivals at ``rate_per_s``
(exponential gaps, kept while they fall inside the window, so the count
of requests varies with the seed as a Poisson count does), lognormal
prompt lengths and token budgets, clipped, and the token ids.  The same
seed and file give the same schedule.

Parameters (all in the traffic file)::

    rate_per_s          arrivals per second, fixed in the cell
    prompt_median, prompt_sigma, prompt_min, prompt_max
    budget_median, budget_sigma, budget_min, budget_max

Needs numpy only; a served cell's parent stays off JAX.
"""

from __future__ import annotations

from typing import List

import numpy as np


def _lognormal(rng, n, median, sigma, lo, hi):
    values = rng.lognormal(np.log(median), sigma, n)
    return np.clip(np.rint(values), lo, hi).astype(int)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int
             ) -> List[dict]:
    """Requests in due order: ``{"due_s", "prompt", "budget"}``."""
    rng = np.random.default_rng(int(seed))
    rate = traffic["rate_per_s"]
    # Far more gaps than the window can hold (mean + 10 sigma), cut at
    # the window's end.
    mean = rate * seconds
    due = np.cumsum(rng.exponential(
        1.0 / rate, int(mean + 10 * np.sqrt(mean) + 10)))
    due = due[due < seconds]
    n = len(due)
    prompts = _lognormal(rng, n, traffic["prompt_median"],
                         traffic["prompt_sigma"], traffic["prompt_min"],
                         traffic["prompt_max"])
    budgets = _lognormal(rng, n, traffic["budget_median"],
                         traffic["budget_sigma"], traffic["budget_min"],
                         traffic["budget_max"])
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompts[i])).tolist(),
             "budget": int(budgets[i])}
            for i in range(n)]
