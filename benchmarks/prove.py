"""Where the limits and the rate of a cell come from: runs on the chip that
the benchmark's own runs do not make.

    python3 benchmarks/prove.py [--manifest <draft.json>] limits \\
        --workload <cell> --seeds 1,2,3 [--control 3] [--seconds 0]
    python3 benchmarks/prove.py [--manifest <draft.json>] sweep \\
        --workload <cell> --rates 0.8,1.0 --seconds 40 --seed 5

`limits` runs the cell once per seed in this one process and prints every
number `correct` compares (the lower reading of a limit is the largest over
the seeds). For the first `--control` seeds it then puts the reference in
the program's place in the next precision below (fp8 for a bf16
configuration), and for a training cell plants each fault in the reference
too; every one of them goes through the harness's own comparison
(`Run.check` against the configuration's limits) and has to come out
`correct: False`, or the command exits 1. `sweep` runs an open-loop cell at
each rate and prints what decides the knee. Everything is appended to
chiprun_out/prove.jsonl.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import run as R          # noqa: E402


def _cell(name, manifest_path):
    """A cell of BENCHMARK.json, or of the draft manifest that a cell is
    proven from before it goes in."""
    manifest = R.load(R.ROOT, manifest_path)
    cell = {w["name"]: w for w in manifest["workloads"]}[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return (manifest, cell, R.load(R.ROOT, entry["file"]),
            R.load(R.HERE, "traffic", cell["traffic"] + ".json"))


def _emit(record):
    print(json.dumps(record), flush=True)
    os.makedirs(os.path.join(R.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(R.ROOT, "chiprun_out", "prove.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")


def _judged(what, cell, config, traffic, seed, fill):
    """One control or fault held to the configuration's limits exactly as
    a run is: `fill(run)` makes the harness's own `check` calls on a Run
    of this cell. Returns whether it came out not correct."""
    run = R.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                seconds=0.0, trace=False)
    fill(run)
    print(f"{what}, seed {seed}:", file=sys.stderr)
    run.report()
    _emit({"what": what, "cell": cell["name"], "seed": seed,
           "correct": run.correct(), "notes": run.notes,
           "checks": {n: {"value": v, "limit": lim}
                      for n, v, lim in run.checks}})
    return not run.correct()


def limits(args):
    manifest, cell, config, traffic = _cell(args.workload, args.manifest)
    seeds = [int(s) for s in args.seeds.split(",")]
    kept, caught = {}, True
    for seed in seeds:
        line, run = R.run_cell(manifest, cell, config, traffic, seed,
                               args.seconds, 0)
        run.report()
        kept[seed] = dict(run.kept)
        _emit({"what": "program", "cell": cell["name"], "seed": seed,
               "correct": line["correct"], "notes": line["notes"],
               "checks": line["checks"]})
    for seed in seeds[: args.control]:
        k = kept[seed]
        if config["runner"] == "train":
            from benchmarks import train
            from benchmarks.reference import decoder

            opt = config["train"]["optimizer"]
            still = dict(opt, learning_rate=0.0)
            for what, kw in (("control_fp8", {"mode": "fp8"}),
                             ("fault_unchanged_state", {"opt": still}),
                             ("fault_half_batch", {"half_batch": True})):
                got = decoder.train_steps(config, seed, k["fed"],
                                          kw.pop("opt", opt), **kw)
                caught &= _judged(
                    what, cell, config, traffic, seed,
                    lambda run: train.compare(run, got, k["ref"],
                                              config["limits"]))
        else:
            from benchmarks import serve

            gap = serve.widest_gap(config, seed, k["sequences"], mode="fp8")
            caught &= _judged(
                "control_fp8", cell, config, traffic, seed,
                lambda run: run.check(
                    "served_logit_gap_max", gap,
                    config["limits"]["served_logit_gap_max"]))
    if not caught:
        raise SystemExit("prove: a control or a fault came out correct")


def sweep(args):
    manifest, cell, config, traffic = _cell(args.workload, args.manifest)
    from benchmarks import reduce as red

    # the sweep reads queues and times; its output check is the longest
    # finished request alone
    config = dict(config, sample={"requests": 1, "served_tokens": 1,
                                  "tokens": 1 << 30})
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        line, run = R.run_cell(
            manifest, cell, config, dict(traffic, rate_per_s=rate),
            args.seed + i, args.seconds, 0)
        q = lambda name, p: red.quantile(run.series.get(name, []), p)
        c = run.counts
        _emit({"what": "sweep", "cell": cell["name"], "rate_per_s": rate,
               "seed": args.seed + i, "correct": line["correct"],
               "attempted": line["attempted"], "failed": line["failed"],
               "ttft_p50_ms": q("ttft_ms", 0.5), "ttft_p90_ms": q("ttft_ms", 0.9),
               "itl_p50_ms": q("itl_ms", 0.5), "itl_p99_ms": q("itl_ms", 0.99),
               "step_p50_ms": q("engine_step_ms", 0.5),
               "generated_tokens_per_s": c.get("generated_tokens", 0) / c["window_s"],
               **{k: c.get(k) for k in (
                   "unfinished_at_open", "unfinished_at_close",
                   "waiting_at_open", "waiting_at_close", "setup_s")},
               "checks": {n: v["value"] for n, v in line["checks"].items()}})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="a draft manifest, for a cell not yet proven")
    sub = ap.add_subparsers(dest="cmd", required=True)
    a = sub.add_parser("limits")
    a.add_argument("--workload", required=True)
    a.add_argument("--seeds", required=True)
    a.add_argument("--control", type=int, default=3)
    a.add_argument("--seconds", type=float, default=0.0)
    a.set_defaults(fn=limits)
    b = sub.add_parser("sweep")
    b.add_argument("--workload", required=True)
    b.add_argument("--rates", required=True)
    b.add_argument("--seconds", type=float, default=40.0)
    b.add_argument("--seed", type=int, default=1)
    b.set_defaults(fn=sweep)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
