"""From what a run recorded to the numbers it reports.

Two halves. `summarize_trace` reads the profiler's xplane file with
jax.profiler.ProfileData alone: device busy and idle time, device time by
program and by operation, and the idle gaps named by the harness span
(jax.profiler.TraceAnnotation) that the host was in. The reducers turn a
run's `Readings` (series of samples, counts, the trace summary) into one
metric each; benchmarks/metrics/<metric>.json names the reducer and its
arguments. A reducer that finds nothing to read returns None and the metric
is left out of the line; a reducer that is not built in is a file
benchmarks/reducers/<name>.py with a function `read(readings, **args)`.

    python benchmarks/reduce.py <file.xplane.pb>     # describe a trace
"""
from __future__ import annotations

import bisect
import dataclasses
import importlib
import re
import sys
from collections import defaultdict

NO_SPAN = "_no_benchmark_span_"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_TEXT_STATS = ("tf_op", "hlo_op", "long_name", "name", "hlo_category")


# ------------------------------------------------------------------ trace
@dataclasses.dataclass
class TraceSummary:
    window_s: float = 0.0
    busy_s: float = 0.0                 # mean over the device planes
    devices: int = 0
    # device 0 only, seconds
    program_calls: dict = dataclasses.field(default_factory=dict)
    op_self: dict = dataclasses.field(default_factory=dict)
    ops: list = dataclasses.field(default_factory=list)
    idle_gaps: dict = dataclasses.field(default_factory=dict)
    span_walls: dict = dataclasses.field(default_factory=dict)
    span_busy: dict = dataclasses.field(default_factory=dict)


def union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(merged, a, b):
    """Length of [a, b] covered by a merged interval list."""
    i = bisect.bisect_left(merged, (a, a)) - 1
    total = 0.0
    for s, e in merged[max(i, 0):]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def self_times(events):
    """[(start, end, key)] that may nest -> {key: time not covered by a
    child}; an operation that spans others (a loop, a call) keeps only its
    own part."""
    out = defaultdict(float)
    stack = []                           # (end, key, child_time, dur)
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, key, child, dur = stack.pop()
            out[key] += max(0.0, dur - child)
            if stack:
                stack[-1][2] += dur
    for s, e, key in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        stack.append([e, key, 0.0, e - s])
    close(float("inf"))
    return dict(out)


def op_label(name):
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3:fusion`."""
    lhs, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][a-z\-]+)\(", " " + rest)
    return lhs.lstrip("%") + (":" + m.group(1) if m else "")


def summarize_events(device_lines, host_spans, span_names):
    """The reduction proper, over plain tuples so that a test can feed it
    a synthetic trace. `device_lines`: {device index: {"ops": [(start_s,
    end_s, name, text)], "modules": [(start_s, end_s, name)]}};
    `host_spans`: [(start_s, end_s, name)]."""
    t = TraceSummary(devices=len(device_lines))
    if not device_lines:
        return t
    bounds = [x for d in device_lines.values() for line in d.values()
              for ev in line for x in ev[:2]]
    spans = [s for s in host_spans if s[2] in span_names]
    bounds += [x for s in spans for x in s[:2]]
    if not bounds:
        return t
    w0, w1 = min(bounds), max(bounds)
    t.window_s = w1 - w0
    busy_by_dev = {}
    for idx, d in device_lines.items():
        evs = d.get("ops") or d.get("modules") or []
        busy_by_dev[idx] = union([(e[0], e[1]) for e in evs])
    t.busy_s = sum(sum(b - a for a, b in u)
                   for u in busy_by_dev.values()) / len(busy_by_dev)
    first = min(device_lines)
    d0, busy0 = device_lines[first], busy_by_dev[first]
    modules = sorted(d0.get("modules", []))
    starts = [m[0] for m in modules]
    calls = defaultdict(list)
    for s, e, name in modules:
        calls[name].append(e - s)
    t.program_calls = dict(calls)

    def module_of(ts):
        i = bisect.bisect_right(starts, ts) - 1
        if i >= 0 and ts <= modules[i][1]:
            return modules[i][2]
        return ""

    keyed = []
    for s, e, name, text in d0.get("ops", []):
        prog = module_of(s)
        t.ops.append((s, e, prog, name + " " + text))
        keyed.append((s, e, f"{prog}:{op_label(name)}"))
    t.op_self = self_times(keyed)
    # idle gaps of the first device, by the harness span the host was in
    spans.sort()
    span_starts = [s[0] for s in spans]
    gaps, prev = [], w0
    for a, b in busy0 + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = defaultdict(float)
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(span_starts, a) - 1, 0)
        for s, e, name in spans[i:]:
            if s >= b:
                break
            part = max(0.0, min(e, b) - max(s, a))
            named[name] += part
            covered += part
        named[NO_SPAN] += max(0.0, (b - a) - covered)
    t.idle_gaps = {k: v for k, v in named.items() if v > 0}
    walls, inside = defaultdict(list), defaultdict(float)
    for s, e, name in spans:
        walls[name].append(e - s)
        inside[name] += overlap(busy0, s, e)
    t.span_walls, t.span_busy = dict(walls), dict(inside)
    return t


def read_xplane(path):
    """(device_lines, host_spans) of an .xplane.pb, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_lines, host_spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lines["ops"] = [
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name,
                         " ".join(str(v) for k, v in ev.stats
                                  if k in _TEXT_STATS))
                        for ev in line.events]
                elif line.name == "XLA Modules":
                    lines["modules"] = [
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         re.sub(r"\(\d+\)$", "", ev.name))
                        for ev in line.events]
            device_lines[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host_spans.append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
    return device_lines, host_spans


def summarize_trace(path, span_names):
    device_lines, host_spans = read_xplane(path)
    return summarize_events(device_lines, host_spans, set(span_names))


def breakdown(t, top=10):
    """The operations that took most device time, the same operation of
    every layer summed (`fusion.12` and `fusion.40` of one program are two
    instances of `fusion`), and the longest idle gaps by harness span."""
    summed = defaultdict(float)
    for key, seconds in t.op_self.items():
        summed[re.sub(r"\.\d+", "", key)] += seconds
    ops = sorted(summed.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(t.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[_short(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def _short(name):
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name)[:96]


# --------------------------------------------------------------- readings
@dataclasses.dataclass
class Readings:
    series: dict                  # name -> [samples]
    counts: dict                  # name -> number
    cell: dict                    # config, traffic, chips
    peaks: dict                   # this device's row of peaks.json
    trace: TraceSummary | None = None


def quantile(values, q):
    """Exact quantile with linear interpolation between order statistics
    (numpy's default), over all samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def _matching(t, programs=None, ops=None):
    """Device-0 op events of the trace whose program and text match."""
    pr = re.compile(programs) if programs else None
    orx = re.compile(ops) if ops else None
    return [(s, e) for s, e, prog, text in t.ops
            if (pr is None or pr.search(prog))
            and (orx is None or orx.search(text))]


def r_value(r, count, scale=1.0):
    v = r.counts.get(count)
    return None if v is None else v * scale


def r_quantile(r, series, q):
    return quantile(r.series.get(series, []), q)


def r_mean(r, series):
    xs = r.series.get(series, [])
    return sum(xs) / len(xs) if xs else None


def r_rate(r, count, per, scale=1.0):
    n, d = r.counts.get(count), r.counts.get(per)
    return None if not n or not d else scale * n / d


def r_mfu(r, flops="required_flops", seconds="window_s"):
    """Required operations over what the chips could do in the time."""
    n, d = r.counts.get(flops), r.counts.get(seconds)
    if not n or not d:
        return None
    return 100.0 * n / (d * r.cell["chips"] * r.peaks["bf16_flops"])


def r_program_ms(r, programs, stat="median"):
    """Device time of one call of the programs matching, in ms."""
    if r.trace is None:
        return None
    rx = re.compile(programs)
    xs = [d for name, ds in r.trace.program_calls.items()
          if rx.search(name) for d in ds]
    if not xs:
        return None
    return 1e3 * (quantile(xs, 0.5) if stat == "median" else sum(xs))


def r_program_ms_per(r, programs, count, scale=1.0):
    """Summed device time of the matching programs over a count."""
    total, n = r_program_ms(r, programs, "sum"), r.counts.get(count)
    return None if total is None or not n else scale * total / n


def r_span_host_ms(r, span):
    """Wall time of a harness span less the device's busy time inside it,
    a call: what the host adds to a step."""
    if r.trace is None or not r.trace.span_walls.get(span):
        return None
    walls = r.trace.span_walls[span]
    return 1e3 * (sum(walls) - r.trace.span_busy[span]) / len(walls)


def _kernel(name):
    return importlib.import_module(f"benchmarks.kernels.{name}")


def r_kernel_roofline(r, kernel):
    """Least time the chip could take for the kernel's required work over
    the device time of its calls."""
    if r.trace is None:
        return None
    k = _kernel(kernel)
    evs = _matching(r.trace, k.PROGRAMS, k.OPS)
    least = k.least_seconds(r.counts, r.cell, r.peaks)
    if not evs or not least:
        return None
    return 100.0 * least / sum(e - s for s, e in evs)


def r_kernel_time_share(r, kernel):
    if r.trace is None or not r.trace.busy_s:
        return None
    k = _kernel(kernel)
    evs = _matching(r.trace, k.PROGRAMS, k.OPS)
    if not evs:
        return None
    return 100.0 * sum(e - s for s, e in evs) / r.trace.busy_s


REDUCERS = {
    "value": r_value, "quantile": r_quantile, "mean": r_mean,
    "rate": r_rate, "mfu": r_mfu, "program_ms": r_program_ms,
    "program_ms_per": r_program_ms_per, "span_host_ms": r_span_host_ms,
    "kernel_roofline": r_kernel_roofline,
    "kernel_time_share": r_kernel_time_share,
}


def reduce_metric(spec, readings):
    """One metric from its file's {"reducer": ..., "args": {...}}."""
    name = spec["reducer"]
    fn = REDUCERS.get(name)
    if fn is None:
        fn = importlib.import_module(f"benchmarks.reducers.{name}").read
    return fn(readings, **spec.get("args", {}))


def describe(path, limit=12):
    """What a trace holds, for whoever writes a pattern against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                stats = {k: str(v)[:80] for k, v in ev.stats}
                print(f"    {ev.start_ns} +{ev.duration_ns} "
                      f"{ev.name[:100]!r} {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
