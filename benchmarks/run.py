"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the
configuration's file (BENCHMARK.json gives it), benchmarks/traffic/
<traffic>.json, benchmarks/metrics/<metric>.json for every metric and
benchmarks/kernels/<kernel>.py for a kernel's required work. The
configuration names its runner (benchmarks/<runner>.py: `train`, `serve`),
the traffic file's `kind` names the runner's driver loop. No cell is named
in code.

The last line of standard output is the result; the numbers `correct` was
decided on are its last key and the last lines of standard error. Off the
chip the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()            # the process's start, near enough

import argparse                      # noqa: E402
import dataclasses                   # noqa: E402
import glob                          # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmarks")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """What a runner is given, and the places it records into."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float = T0
    series: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace_dir: str = ""
    span_names: tuple = ()
    notes: list = dataclasses.field(default_factory=list)
    tracing: bool = False
    kept: dict = dataclasses.field(default_factory=dict)

    def add(self, name, value):
        self.series.setdefault(name, []).append(value)

    def count(self, name, by=1):
        self.counts[name] = self.counts.get(name, 0) + by

    def check(self, name, value, limit):
        """One number compared, beside its limit: correct needs
        value <= limit (a NaN is not)."""
        self.checks.append((name, float(value), float(limit)))

    def correct(self):
        return bool(self.checks) and all(
            v <= lim for _, v, lim in self.checks)

    def report(self):
        """Every number compared beside its limit, then the verdict: the
        last lines a run writes to standard error."""
        file = sys.stderr
        for name, v, lim in self.checks:
            print(f"check {name}: {v:.6g} (limit {lim:.6g})"
                  f"{'' if v <= lim else '  <-- FAILS'}", file=file)
        print(f"correct: {self.correct()}", file=file, flush=True)

    def open_window(self):
        """Set-up ends here; a traced run's profiler starts first."""
        if self.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=options)
            self.tracing = True
        now = time.perf_counter()
        self.counts["setup_s"] = now - self.t0
        return now

    def close_window(self):
        if self.tracing:
            import jax

            self.tracing = False
            jax.profiler.stop_trace()

    def read_memory_peak(self):
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[: self.cell["chips"]]]
        self.memory_peak_bytes = int(max(peaks))


def span(name):
    """A harness span on the profiler's clock (nothing when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def find_device(chips, require_chip=True):
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if require_chip and (dev.platform != "tpu" or device["count"] < chips):
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX reports "
            f"{device}. Nothing is measured off the chip.")
    return device


def peaks_for(device, require_chip=True):
    table = load(HERE, "peaks.json")
    if device["kind"] not in table:
        if require_chip:
            raise SystemExit(
                f"benchmark: no published peaks for {device['kind']!r} in "
                "benchmarks/peaks.json; add the row with its source")
        return None
    return table[device["kind"]]


def cell_metrics(manifest, cell, group):
    """The metrics of `group` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def run_cell(manifest, cell, config, traffic, seed, seconds, trace,
             require_chip=True, metric_dir=None):
    """Drive one cell; returns its result line (a dict) and the Run it was
    read from (benchmarks/prove.py reads what that kept). `require_chip`
    False is for the tests, which drive the control flow on the CPU: the
    line then carries no device metric at all."""
    from benchmarks import reduce as R

    device = find_device(cell["chips"], require_chip)
    peaks = peaks_for(device, require_chip)
    run = Run(cell=cell, config=config, traffic=traffic, seed=int(seed),
              seconds=float(seconds), trace=bool(trace),
              trace_dir=os.path.join(ROOT, ".bench_trace", cell["name"]))
    runner = importlib.import_module(f"benchmarks.{config['runner']}")
    try:
        runner.run(run)
        summary = None
        if run.trace:
            files = glob.glob(os.path.join(
                run.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if files:
                summary = R.summarize_trace(files[0], run.span_names)
    finally:
        run.close_window()
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    readings = R.Readings(series=run.series, counts=run.counts,
                          cell={"config": config, "traffic": traffic,
                                "chips": cell["chips"]},
                          peaks=peaks, trace=summary)
    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(manifest, cell, group):
        spec = load(metric_dir or os.path.join(HERE, "metrics"),
                    m["name"] + ".json")
        value = R.reduce_metric(spec, readings) if peaks else None
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    line = {"attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    if summary is not None and peaks:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = R.breakdown(summary)
    line["notes"] = run.notes
    line = {"correct": run.correct(), **line}
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in run.checks}
    return line, run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: no cell {args.workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load(ROOT, entry["file"])
    traffic = load(HERE, "traffic", cell["traffic"] + ".json")
    line, run = run_cell(manifest, cell, config, traffic, args.seed,
                         args.seconds, args.trace)
    run.report()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
