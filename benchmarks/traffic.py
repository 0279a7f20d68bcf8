"""One general generator for every serving mix: it reads the parameters in
benchmarks/traffic/<traffic>.json and adds nothing of its own, so a later
PR brings a new mix as a data file alone.

What is fixed by the file's `trace_seed` and the same in every run: when
each request is due and how long its prompt and its answer are (replaying a
recorded trace is how serving is benchmarked in the field: Mooncake, the
Azure traces). What `--seed` draws: the token ids, and the model's weights.
So two runs of one cell send the same requests at the same instants, and
two seeds differ in nothing that changes the amount of work.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    index: int            # position in the trace (or in a client's list)
    due_s: float          # offset from the trace's start (open loop)
    prompt_len: int
    output_len: int
    client: int = 0


def _lengths(rng, spec, n):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(rng, spec, n):
    """Inter-arrival gaps with unit mean: a Poisson process."""
    process = spec.get("process", "poisson")
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    return rng.exponential(1.0, n)


def _streams(trace_seed):
    """One stream each for arrivals, prompt lengths and answer lengths,
    so that request i is the same whatever the rate."""
    return [np.random.default_rng([int(trace_seed), k]) for k in range(3)]


def open_loop_trace(spec):
    """The whole trace of an `open_loop_trace` file: arrivals over
    `horizon_s` at `rate_per_s`, one fixed realisation."""
    arrive, prompt, answer = _streams(spec["trace_seed"])
    n = int(spec["horizon_s"] * spec["rate_per_s"] * 2) + 16
    due = np.cumsum(_gaps(arrive, spec.get("arrivals", {}), n)) / spec[
        "rate_per_s"]
    prompts = _lengths(prompt, spec["prompt_tokens"], n)
    outputs = _lengths(answer, spec["output_tokens"], n)
    return [Planned(i, float(due[i]), int(prompts[i]), int(outputs[i]))
            for i in range(n) if due[i] < spec["horizon_s"]]


def closed_loop_lists(spec):
    """`closed_loop_list`: for each client the fixed list it works
    through, its next request sent when the last one's answer returns."""
    _, prompt, answer = _streams(spec["trace_seed"])
    clients, per = spec["clients"], spec["per_client"]
    prompts = _lengths(prompt, spec["prompt_tokens"], clients * per)
    outputs = _lengths(answer, spec["output_tokens"], clients * per)
    return [[Planned(c * per + j, 0.0, int(prompts[c * per + j]),
                     int(outputs[c * per + j]), client=c)
             for j in range(per)] for c in range(clients)]


def trace_bytes(spec):
    """The trace as bytes, for the test that two generations agree."""
    plan = (open_loop_trace(spec) if spec["kind"] == "open_loop_trace"
            else [p for lst in closed_loop_lists(spec) for p in lst])
    return repr(plan).encode()


def prompt_ids(seed, planned, vocab):
    """The request's token ids, from --seed and its place in the trace."""
    rng = np.random.default_rng([int(seed), planned.index])
    return [int(t) for t in rng.integers(0, vocab, planned.prompt_len)]
