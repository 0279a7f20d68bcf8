"""Share of the device's busy time spent under one `jax.named_scope` of
the program (`attention`, `mlp`, `lm_head_loss`, `optimizer`, `embedding`).

    {"reducer": "scope_time_share", "args": {"scope": "attention"}}

Self time (reduce.self_times) of device-0 operations whose text holds the
scope as a component of the operation's path (`.../attention/...`), over
the busy time. An operation is counted under the first scope of ORDER that
its text holds, and under `unscoped` if it holds none, so the shares of
ORDER and `unscoped` add to 100.

No metric of BENCHMARK.json uses this yet. The path is the operation's
`op_name`, which the TPU's xplane keeps in the event metadata's stats
(`tf_op`) and in the HLO module of the `/host:metadata` plane;
jax.profiler.ProfileData hands out an event's own stats alone, so
reduce.read_xplane gives a TraceSummary whose texts name instructions
(`%fusion.104 = ...`) and no path, and every share would read `unscoped`
100. PERF.md section 7 says what the reader needs; this file and its
test fix the arithmetic until then, and `read` gives None while no text
holds any scope, so that a share is never reported from names alone.
"""
from __future__ import annotations

import re

from benchmarks import reduce as R

ORDER = ("optimizer", "lm_head_loss", "attention", "mlp")
UNSCOPED = "unscoped"


def _scope_of(text):
    for scope in ORDER:
        if re.search(r"(?:^|[\s/])" + scope + r"/", text):
            return scope
    return UNSCOPED


def read(readings, scope):
    t = readings.trace
    if t is None or not t.busy_s or not t.ops:
        return None
    own = R.self_times(
        [(s, e, _scope_of(text)) for s, e, _, text in t.ops])
    if set(own) <= {UNSCOPED}:
        return None
    return 100.0 * own.get(scope, 0.0) / t.busy_s
