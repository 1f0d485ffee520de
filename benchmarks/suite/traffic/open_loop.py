"""Open-loop request traffic on the wall clock, from parameters alone:
Poisson arrivals, log-normal lengths, nothing shared.

Parameters (a workload file's ``traffic`` block):

- ``rate_per_s``: offered requests per second, fixed in the cell;
- ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}``:
  log-normal lengths, clipped;
- ``max_total``: prompt + output never exceeds it (the output is cut);
- ``ramp_s``, ``drain_s``: arrivals start ``ramp_s`` before the window,
  and the run ends ``drain_s`` after it.

**One cell is one fixed trace.** The arrival times (exponential gaps,
scaled so that the ``n`` arrivals span exactly ``n / rate`` seconds) and
the set of sizes are drawn once, from ``TRACE_SEED`` and the parameters,
and are the same in every run of the cell. ``--seed`` reorders the sizes
inside blocks of ``BLOCK`` consecutive arrivals and draws the tokens
(and, in the driver, the weights). So the runs of a cell are replicas of
one trace, not samples of the arrival process: a result says how the
system serves *this* trace, and the spread between runs is the system's,
not the traffic's. (With sizes shuffled over the whole run the work that
fell into a 30 s window swung by 10 % between seeds, more than any bound
the benchmark may set: a request stays about 20 s, so a window holds
only a few independent samples of the batch.) Another trace is another
cell, with its own parameters.
"""

import dataclasses

import numpy as np

TRACE_SEED = 23     # the arrival times and the set of sizes of every cell
BLOCK = 4           # --seed moves a size at most this many arrivals


@dataclasses.dataclass
class Arrival:
    rid: str
    due_s: float            # seconds after the start of the ramp
    prompt: list
    max_new_tokens: int


def _lengths(rng, n, spec):
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def horizon_s(params, seconds):
    return params["ramp_s"] + seconds + params["drain_s"]


def make(params, seed, vocab_size, seconds, **_):
    """The run's arrivals, in order of ``due_s``."""
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * horizon_s(params, seconds))))
    trace = np.random.default_rng(TRACE_SEED)
    gaps = trace.exponential(1.0, n)
    gaps *= (n / rate) / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]
    prompts = _lengths(trace, n, params["prompt"])
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
