"""The training runner of the Granite 4.0-H configuration: train.run's
control flow (set-up, the traffic file's warm-up steps of which the first
are followed, the window, exact-count checks, the reference once the
program's state is freed) around models/granite_hybrid.py, with leaves,
reference and required work from weights_granite_hybrid.py,
reference/granite_hybrid.py and work_granite_hybrid.py. train.py and
train_hybrid.py are each pinned to one family by their module's `W`,
`work` and reference; what of them can be imported is: the seeded rows,
the followed batches and the fed-rows count and the spans (train.py), the
comparison with its median leaf (train_hybrid.py). The optimizer's linear
warm-up is train_hybrid.py's recipe, from the configuration.

    python3 benchmarks/train_granite_hybrid.py limits --workload <cell> \\
        --seeds 1,2,3 [--control 3] [--manifest <draft.json>]

is prove.py's `limits` for this runner: the program on every seed, then
for the first `--control` seeds the fp8 control and the planted faults
(state unchanged, half the batch, and this model's own: the recurrence
left out, y = D x), each judged by prove._judged and each to come out not
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
from benchmarks import weights_granite_hybrid as W      # noqa: E402
from benchmarks import work_granite_hybrid as work      # noqa: E402
from benchmarks.run import span                         # noqa: E402
from benchmarks.train import (SPANS, SeededRows,        # noqa: E402
                              followed_batches, rows_that_differ)
from benchmarks.train_hybrid import compare             # noqa: E402

MODEL_KEYS = (
    "vocab_size", "hidden_size", "shared_intermediate_size",
    "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_d_conv", "mamba_expand", "mamba_n_groups", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
    "residual_multiplier", "attention_multiplier", "logits_scaling",
    "rms_norm_eps", "tie_word_embeddings", "num_local_experts",
    "position_embedding_type", "initializer_range")


def model_config(cfg, **extra):
    """GraniteHybridConfig arguments from the source's keys."""
    return {**{k: cfg[k] for k in MODEL_KEYS}, **extra}


def build_model(cfg, seed, **extra):
    """The program's model holding the seed's weights: every parameter is
    created from the array weights_granite_hybrid made for it, so no
    second copy of the model ever exists."""
    import jax.numpy as jnp

    from paddle_tpu.models import (GraniteHybridConfig,
                                   GraniteHybridForCausalLM)
    from paddle_tpu.nn import initializer as I

    made = W.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    queue = list(made.items())

    def hand_out(shape, dtype=None):
        name, array = queue.pop(0)
        if tuple(shape) != array.shape:
            raise RuntimeError(
                f"weights_granite_hybrid.leaf_specs is out of step with "
                f"the model: {name} is {array.shape}, the model asked for "
                f"{tuple(shape)}")
        return array

    with I.param_init_override(hand_out, dtype=cfg["torch_dtype"]):
        model = GraniteHybridForCausalLM(
            GraniteHybridConfig(**model_config(cfg, **extra)))
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


def record_work(run, cfg, seq, batch):
    """The counts the per-layer metrics read: the step's required
    operations and the selective scan's own work, forward and backward."""
    tokens = batch * seq
    steps = run.attempted
    run.counts["steps"] = steps
    run.counts["tokens_per_chip"] = tokens * steps / run.cell["chips"]
    run.counts["required_flops"] = (
        work.train_flops_per_token(cfg, seq) * tokens * steps)
    for side, backward in (("fwd", False), ("bwd", True)):
        ops, nbytes = work.scan_work(cfg, tokens * steps, backward)
        run.counts[f"ssd_{side}_flops"] = ops
        run.counts[f"ssd_{side}_bytes"] = nbytes


def run(run):
    import jax

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

    def one_step():
        t0 = time.perf_counter()
        with span("data.next"):
            ids = next(feed)
        t1 = time.perf_counter()
        warmup.step()
        with span("train.step"):
            loss = step(ids)
            jax.block_until_ready(loss._data)
        return ids, loss, t1 - t0, time.perf_counter() - t1

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
    record_work(run, cfg, seq, batch)
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    run.check("compiles_in_window", len(compiles), 0)
    run.check("failed_steps", run.failed, 0)
    run.read_memory_peak()
    # the program's state goes before the reference comes
    del step, opt, model, feed, loader, loss
    gc.collect()
    from benchmarks.reference import granite_hybrid as reference

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
            ("fault_recurrence_left_out", {"drop_scan": True}))


def control_readings(config, seed, fed, what):
    """The reference put in the program's place with one control or fault
    planted: what `compare` is then given as `got`."""
    from benchmarks.reference import granite_hybrid as reference

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
        raise SystemExit("train_granite_hybrid: a control or a fault came "
                         "out correct")


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
