"""``open_loop`` with prompts of several classes in one queue: the same
Poisson arrivals, log-normal lengths and one fixed trace
(`traffic/open_loop.py` says why), but a request's prompt is drawn from
one of ``classes``, chosen a request from the trace's own seed.

Parameters (a workload file's ``traffic`` block): ``open_loop``'s, with
``classes`` in ``prompt``'s place: a list of ``{"share", "prompt":
{"median", "sigma", "min", "max"}}`` whose shares add up to 1. ``output``
and ``max_total`` are every class's.
"""

import numpy as np

from benchmarks.suite.traffic.open_loop import (BLOCK, TRACE_SEED, Arrival,
                                                _lengths, horizon_s)

__all__ = ["make", "horizon_s", "Arrival"]


def make(params, seed, vocab_size, seconds, **_):
    """The run's arrivals, in order of ``due_s``."""
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * horizon_s(params, seconds))))
    trace = np.random.default_rng(TRACE_SEED)
    gaps = trace.exponential(1.0, n)
    gaps *= (n / rate) / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]
    classes = params["classes"]
    shares = np.asarray([c["share"] for c in classes], float)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"the classes' shares add up to {shares.sum()}")
    which = trace.choice(len(classes), size=n, p=shares)
    by_class = [_lengths(trace, n, c["prompt"]) for c in classes]
    prompts = np.choose(which, by_class)
    outputs = _lengths(trace, n, params["output"])
    outputs = np.maximum(np.minimum(outputs,
                                    params["max_total"] - prompts), 1)

    rng = np.random.default_rng(seed)
    order = np.concatenate([lo + rng.permutation(min(BLOCK, n - lo))
                            for lo in range(0, n, BLOCK)])
    prompts, outputs = prompts[order], outputs[order]
    return [Arrival(rid=f"r{i}", due_s=float(due[i]),
                    prompt=rng.integers(0, vocab_size,
                                        int(prompts[i])).tolist(),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]
