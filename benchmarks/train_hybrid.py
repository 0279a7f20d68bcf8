"""The training runner for a block that benchmarks/train.py's files cannot
describe: train.py, weights.py, work.py and reference/decoder.py are pinned
to the Llama block (`build_model`, `leaf_specs`, `train_flops_per_token`,
`decoder.train_steps`). This one has train.run's control flow (set-up, the
traffic file's warm-up steps of which the first are followed, the window,
exact-count checks, the reference once the program's state is freed) and
takes model, leaf list, reference and work from the files named by the
configuration's family: today Qwen3-Next (models/qwen3_next.py,
weights_qwen3_next.py, reference/qwen3_next.py, work_qwen3_next.py). Of
its own: the optimizer's linear warm-up, the expert layers' load added up
over the window (and held to where it started: `held_rows_drift`), and the
first gradient's gap at the median leaf beside train.compare's worst.

    python3 benchmarks/train_hybrid.py limits --workload <cell> \\
        --seeds 1,2,3 [--control 3] [--manifest <draft.json>]

is prove.py's `limits` for this runner (prove.py sends every runner not
called `train` down the serving branch): the program on every seed, then
for the first `--control` seeds the fp8 control and the planted faults
(state unchanged, half the batch, and this model's own: the held experts'
part left out), each judged by prove._judged and each to come out not
correct.
"""
from __future__ import annotations

import gc
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import weights_qwen3_next as W        # noqa: E402
from benchmarks import work_qwen3_next as work        # noqa: E402
from benchmarks.run import span                       # noqa: E402
from benchmarks import train                          # noqa: E402
from benchmarks.train import (SPANS, SeededRows,      # noqa: E402
                              followed_batches, rows_that_differ)


def model_config(cfg, **extra):
    """Qwen3NextConfig arguments from the source's keys: the router keeps
    its published width, `num_experts` are held here."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "rms_norm_eps",
            "full_attention_interval", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "norm_topk_prob",
            "max_position_embeddings", "initializer_range")
    n = W.dims(cfg)
    return {**{k: cfg[k] for k in keys}, "num_experts": n["experts"],
            "held_experts": (n["held_start"], n["held"]), **extra}


def build_model(cfg, seed, **extra):
    """The program's model holding the seed's weights: every parameter is
    created from the array weights_qwen3_next made for it, so no second
    copy of the model ever exists."""
    import jax.numpy as jnp

    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    from paddle_tpu.nn import initializer as I

    made = W.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    queue = list(made.items())

    def hand_out(shape, dtype=None):
        name, array = queue.pop(0)
        if tuple(shape) != array.shape:
            raise RuntimeError(
                f"weights_qwen3_next.leaf_specs is out of step with the "
                f"model: {name} is {array.shape}, the model asked for "
                f"{tuple(shape)}")
        return array

    with I.param_init_override(hand_out, dtype=cfg["torch_dtype"]):
        model = Qwen3NextForCausalLM(
            Qwen3NextConfig(**model_config(cfg, **extra)))
    if queue:
        raise RuntimeError(f"{len(queue)} leaves were never asked for")
    return model


def _leaf_readings(opt, model, cfg, seed, beta1, want):
    """Per-leaf norms from the optimizer's checkpoint state: `grad` from
    the first moment after step 1, `change` from the master weights
    against the seed's own leaves (train.py's, over this leaf list)."""
    import jax
    import jax.numpy as jnp

    state = opt.state_dict()
    if want == "grad":
        arrays = [state[f"{p.name}_moment1_0"]._data
                  for p in model.parameters()]
    else:       # a float32 configuration has no master copy: the leaf
        arrays = [state.get(f"{p.name}_master_weight_0", p)._data
                  for p in model.parameters()]
    del state
    specs = W.leaf_specs(cfg)
    if want == "grad":
        fn = jax.jit(lambda ms: [
            jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32))))
            / (1 - beta1) for m in ms])
        return {n: float(v) for (n, _, _), v in zip(specs, fn(arrays))}
    key = W.seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out = {}
    for i, (n, shape, kind) in enumerate(specs):
        first = W.make_leaf(key, index=i, shape=shape, kind=kind, std=std,
                            dtype=jnp.dtype(cfg["torch_dtype"]))
        out[n] = float(diff(arrays[i], first))
    return out


def median_leaf_gap(got, ref):
    """train.worst_leaf_gap's gap of a leaf (the two norms' difference
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger), at the median leaf instead of the worst: a
    precision lost everywhere moves every leaf, while the worst leaf is a
    few numbers whose gradient is a cancelling sum and swings from seed
    to seed."""
    median = statistics.median(ref.values())
    return statistics.median(
        abs(got[n] - ref[n]) / max(ref[n], median) for n in ref)


def compare(run, got, ref, limits):
    """train.compare's numbers and the first gradient's median leaf."""
    values = train.compare(run, got, ref, limits)
    values["grad1_median_leaf_gap"] = median_leaf_gap(
        got["grad_norms"], ref["grad_norms"])
    run.check("grad1_median_leaf_gap", values["grad1_median_leaf_gap"],
              limits["grad1_median_leaf_gap"])
    return values


def rows_drift(first, last):
    """How far the rows a layer's held experts are sent moved between the
    last warm-up step and the window's last step: the widest layer's, as
    a share of where it started."""
    first, last = np.asarray(first, float), np.asarray(last, float)
    return float(np.max(np.abs(last - first) / first))


def record_work(run, cfg, seq, batch, rows, imbalance):
    """The counts the per-layer metrics read: required operations, the
    new kernels' work, and the layers' `expert_load` summed over the
    window's steps: `rows` [layers, held], and `imbalance` [layers], each
    step's largest held expert's rows over the mean, summed. The grouped
    products' work is counted at the rows they were sent; `required_flops`
    keeps the experts at the rows a uniform router sends, from shapes."""
    tokens = batch * seq
    steps = run.attempted
    n = W.dims(cfg)
    _, attends = work.layer_kinds(cfg)
    run.counts["steps"] = steps
    run.counts["tokens_per_chip"] = tokens * steps / run.cell["chips"]
    run.counts["required_flops"] = (
        work.train_flops_per_token(cfg, seq) * tokens * steps)
    # work.flash_train_flops multiplies by every layer: one in
    # full_attention_interval attends here
    run.counts["flash_sequences"] = (
        batch * steps * attends / cfg["num_hidden_layers"])
    run.counts["flash_seq_len"] = seq
    rows = np.asarray(rows, np.float64)
    for side, backward in (("fwd", False), ("bwd", True)):
        ops, nbytes = work.delta_rule_work(cfg, tokens * steps, backward)
        run.counts[f"gdr_{side}_flops"] = ops
        run.counts[f"gdr_{side}_bytes"] = nbytes
        ops, nbytes = work.grouped_matmul_work(
            cfg, rows.sum(), steps * cfg["num_hidden_layers"], backward)
        run.counts[f"gmm_{side}_flops"] = ops
        run.counts[f"gmm_{side}_bytes"] = nbytes
    run.counts["moe_held_rows"] = float(rows.sum())
    run.counts["moe_assignments"] = float(
        tokens * n["k"] * cfg["num_hidden_layers"] * steps)
    run.counts["moe_load_max_over_mean"] = float(
        np.max(np.asarray(imbalance, np.float64)) / max(steps, 1))


def run(run):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.compilecache import enable_persistent_cache
    from paddle_tpu.observability import jit_events

    cfg, tr, t = run.config, run.config["train"], run.traffic
    enable_persistent_cache()
    model = build_model(cfg, run.seed, recompute=tr["recompute"],
                        fused_loss_chunk=tr["fused_loss_chunk"])
    batch = tr["batch_per_replica"]
    o = tr["optimizer"]
    # step t of the job runs at learning_rate * t / warmup_steps
    warmup = paddle.optimizer.lr.LinearWarmup(
        o["learning_rate"], o["warmup_steps"], 0.0, o["learning_rate"])
    opt = paddle.optimizer.AdamW(
        learning_rate=warmup, beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), multi_precision=True)

    def loss_fn(m, ids):
        return m(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    seq = t["seq_len"]
    loader = paddle.io.DataLoader(
        SeededRows(run.seed, t["rows"], seq, cfg["vocab_size"]),
        batch_size=batch, num_workers=t["loader_workers"],
        use_shared_memory=True, drop_last=True)
    feed = iter(loader)
    got = {"losses": []}
    followed, fed = t["followed_steps"], []
    mlps = [layer.mlp for layer in model.model.layers]

    @jax.jit
    def add_loads(sums, loads):
        """The step's `expert_load` buffers added to the window's sums on
        the device: nothing of it is read before the window has closed."""
        loads = jnp.stack(loads)
        mean = jnp.maximum(loads.mean(axis=1, dtype=jnp.float32), 1e-9)
        return sums[0] + loads, sums[1] + loads.max(axis=1) / mean, loads

    def no_loads():
        return (jnp.zeros((len(mlps), mlps[0].held[1]), jnp.int32),
                jnp.zeros((len(mlps),), jnp.float32), None)

    sums = no_loads()

    def one_step():
        nonlocal sums
        t0 = time.perf_counter()
        with span("data.next"):
            ids = next(feed)
        t1 = time.perf_counter()
        warmup.step()
        with span("train.step"):
            loss = step(ids)
            jax.block_until_ready(loss._data)
        wall = time.perf_counter() - t1
        sums = add_loads(sums[:2], [m.expert_load._data for m in mlps])
        return ids, loss, t1 - t0, wall

    for i in range(1, t["warmup_steps"] + 1):
        ids, loss, _, wall = one_step()
        run.notes.append(f"warm-up step {i}: {wall:.3f}s")
        if i <= followed:
            fed.append(np.asarray(ids.numpy()))
            got["losses"].append(float(loss.numpy()))
            if i == 1:
                got["grad_norms"] = _leaf_readings(
                    opt, model, cfg, run.seed, o["beta1"], "grad")
            if i == followed:
                got["change_norms"] = _leaf_readings(
                    opt, model, cfg, run.seed, o["beta1"], "change")
    first = np.asarray(sums[2]).sum(axis=1)
    sums = no_loads()
    jit_events.clear_compile_log()
    run.span_names = SPANS
    opened = now = run.open_window()
    while now - opened < run.seconds:
        _, loss, wait, wall = one_step()
        now = time.perf_counter()
        run.attempted += 1
        run.add("input_wait_ms", wait * 1e3)
        run.add("step_ms", wall * 1e3)
        if not np.isfinite(float(loss.numpy())):
            run.failed += 1
    run.close_window()
    run.counts["window_s"] = now - opened
    steps = run.series.get("step_ms", [])
    waits = run.series.get("input_wait_ms", [])
    if steps:       # `limits` runs with an empty window
        run.notes.append(
            f"window {now - opened:.3f}s = steps {sum(steps) / 1e3:.3f}s + "
            f"input wait {sum(waits) / 1e3:.3f}s + rest; steps: median "
            f"{statistics.median(steps):.1f} ms, slowest {max(steps):.1f} ms "
            f"(step {steps.index(max(steps)) + 1}); input wait: slowest "
            f"{max(waits):.1f} ms (step {waits.index(max(waits)) + 1})")
    record_work(run, cfg, seq, batch, sums[0], sums[1])
    last = first if sums[2] is None else np.asarray(sums[2]).sum(axis=1)
    run.notes.append(
        f"expert_load, rows a layer: {[int(x) for x in first]} after the "
        f"warm-up steps, {[int(x) for x in last]} after the last step, in "
        f"passes of {mlps[0].held_rows(batch * seq)}")
    # the cell's traffic is the router's load: a router that leaves the
    # held experts (or crowds them) inside the window is another cell
    run.check("held_rows_drift", rows_drift(first, last),
              run.config["limits"]["held_rows_drift"])
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    run.check("compiles_in_window", len(compiles), 0)
    run.check("failed_steps", run.failed, 0)
    run.read_memory_peak()
    # the program's state goes before the reference comes
    del step, opt, model, mlps, feed, loader, loss
    gc.collect()
    from benchmarks.reference import qwen3_next as reference

    expected = followed_batches(cfg, t, run.seed)
    run.check("fed_rows_differ", rows_that_differ(fed, expected), 0)
    ref = reference.train_steps(cfg, run.seed, expected, o,
                                dtype=cfg["torch_dtype"])
    compare(run, got, ref, run.config["limits"])
    run.kept.update(fed=expected, got=got, ref=ref)
    run.notes.append(f"losses {got['losses']} reference {ref['losses']}")


CONTROLS = (("control_fp8", {"mode": "fp8"}),
            ("fault_unchanged_state", {"still": True}),
            ("fault_half_batch", {"half_batch": True}),
            ("fault_held_experts_left_out", {"drop_held": True}))


def control_readings(config, seed, fed, what):
    """The reference put in the program's place with one control or fault
    planted: what `compare` is then given as `got`."""
    from benchmarks.reference import qwen3_next as reference

    kw = dict(dict(CONTROLS)[what])
    opt = config["train"]["optimizer"]
    if kw.pop("still", False):
        opt = dict(opt, learning_rate=0.0)
    return reference.train_steps(config, seed, fed, opt,
                                 dtype=config["torch_dtype"], **kw)


def limits(args):
    from benchmarks import prove
    from benchmarks import run as R

    manifest, cell, config, traffic = prove._cell(
        args.workload, args.manifest)
    seeds = [int(s) for s in args.seeds.split(",")]
    kept, caught = {}, True
    for seed in seeds:
        line, run_ = R.run_cell(manifest, cell, config, traffic, seed,
                                args.seconds, 0)
        run_.report()
        kept[seed] = dict(run_.kept)
        prove._emit({"what": "program", "cell": cell["name"], "seed": seed,
                     "correct": line["correct"], "notes": line["notes"],
                     "checks": line["checks"]})
    for seed in seeds[: args.control]:
        k = kept[seed]
        for what, _ in CONTROLS:
            got = control_readings(config, seed, k["fed"], what)
            caught &= prove._judged(
                what, cell, config, traffic, seed,
                lambda r: compare(r, got, k["ref"], config["limits"]))
    if not caught:
        raise SystemExit("train_hybrid: a control or a fault came out "
                         "correct")


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--manifest", default="BENCHMARK.json")
    sub = ap.add_subparsers(dest="cmd", required=True)
    a = sub.add_parser("limits")
    a.add_argument("--workload", required=True)
    a.add_argument("--seeds", required=True)
    a.add_argument("--control", type=int, default=3)
    a.add_argument("--seconds", type=float, default=0.0)
    a.set_defaults(fn=limits)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
