"""The training runner of the DeepSeek-V3 configurations (MLA over a
leading dense layer and expert layers behind a sigmoid router): train.run's
control flow (set-up, the traffic file's warm-up steps of which the first
are followed, the window, exact-count checks, the reference once the
program's state is freed) around models/deepseek_v3.py, with leaves,
reference and required work from weights_deepseek_v3.py,
reference/deepseek_v3.py and work_deepseek_v3.py. train.py, train_hybrid.py
and train_granite_hybrid.py are each pinned to one family by their module's
`W`, `work` and reference; what of them can be imported is: the seeded
rows, the followed batches, the fed-rows count and the spans (train.py),
the comparison with its median leaf (train_hybrid.py). The optimizer's
linear warm-up and the expert layers' load added up over the window are
train_hybrid.py's recipe; the held rows' drift is taken against the uniform
share here (`rows_drift`).

    python3 benchmarks/train_deepseek_v3.py limits --workload <cell> \\
        --seeds 1,2,3 [--control 3] [--manifest <draft.json>]

is prove.py's `limits` for this runner: the program on every seed, then
for the first `--control` seeds the fp8 control and the planted faults
(state unchanged, half the batch, and this model's own: the routed
experts left out, the rotary part of the score left out), each judged by
prove._judged and each to come out not correct.
"""
from __future__ import annotations

import gc
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import weights_deepseek_v3 as W         # noqa: E402
from benchmarks import work_deepseek_v3 as work         # noqa: E402
from benchmarks.run import span                         # noqa: E402
from benchmarks.train import (SPANS, SeededRows,        # noqa: E402
                              followed_batches, rows_that_differ)
from benchmarks.train_hybrid import compare              # noqa: E402

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rope_interleave", "rope_scaling",
    "rms_norm_eps", "first_k_dense_replace", "moe_layer_freq",
    "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "scoring_func",
    "max_position_embeddings", "initializer_range")


def model_config(cfg, **extra):
    """DeepseekV3Config arguments from the source's keys: the router keeps
    its published width, `n_routed_experts` are held here."""
    n = W.dims(cfg)
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "n_routed_experts": n["experts"],
            "held_experts": (n["held_start"], n["held"]), **extra}


def build_model(cfg, seed, **extra):
    """The program's model holding the seed's weights: every parameter is
    created from the array weights_deepseek_v3 made for it, so no second
    copy of the model ever exists."""
    import jax.numpy as jnp

    from paddle_tpu.models import DeepseekV3Config, DeepseekV3ForCausalLM
    from paddle_tpu.nn import initializer as I

    made = W.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    queue = list(made.items())

    def hand_out(shape, dtype=None):
        name, array = queue.pop(0)
        if tuple(shape) != array.shape:
            raise RuntimeError(
                f"weights_deepseek_v3.leaf_specs is out of step with the "
                f"model: {name} is {array.shape}, the model asked for "
                f"{tuple(shape)}")
        return array

    with I.param_init_override(hand_out, dtype=cfg["torch_dtype"]):
        model = DeepseekV3ForCausalLM(
            DeepseekV3Config(**model_config(cfg, **extra)))
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


def rows_drift(first, last, uniform):
    """How far the rows a layer's held experts are sent moved between the
    last warm-up step and the window's last step: the widest layer's, as a
    share of the rows a uniform router sends a layer's held experts. 1
    reads: a layer lost a uniform router's whole share (the router left
    the held experts) or gained it (one pass of `MoELayer.held_rows` no
    longer holds the step). train_hybrid.rows_drift divides by where the
    layer started; behind this router a layer starts anywhere from 0.7 to
    1.3 of the uniform share, and that quotient swings with its
    denominator (0.12 to 0.66 over eleven seeds where this reads 0.10 to
    0.45: PERF.md section 6)."""
    first, last = np.asarray(first, float), np.asarray(last, float)
    return float(np.max(np.abs(last - first)) / uniform)


def record_work(run, cfg, seq, batch, rows, imbalance):
    """The counts the per-layer metrics read: required operations, the MLA
    kernels' work, and the expert layers' `expert_load` summed over the
    window's steps: `rows` [expert layers, held], and `imbalance` [expert
    layers], each step's largest held expert's rows over the mean, summed.
    The grouped products' work is counted at the rows they were sent;
    `required_flops` keeps the experts at the rows a uniform router sends,
    from shapes."""
    tokens = batch * seq
    steps = run.attempted
    n = W.dims(cfg)
    _, expert_layers = work.layer_kinds(cfg)
    run.counts["steps"] = steps
    run.counts["tokens_per_chip"] = tokens * steps / run.cell["chips"]
    run.counts["required_flops"] = (
        work.train_flops_per_token(cfg, seq) * tokens * steps)
    rows = np.asarray(rows, np.float64)
    for side, backward in (("fwd", False), ("bwd", True)):
        ops, nbytes = work.attention_core_work(
            cfg, batch * steps, seq, backward)
        run.counts[f"mla_{side}_flops"] = ops
        run.counts[f"mla_{side}_bytes"] = nbytes
        ops, nbytes = work.grouped_matmul_work(
            cfg, rows.sum(), steps * expert_layers, backward)
        run.counts[f"gmm_{side}_flops"] = ops
        run.counts[f"gmm_{side}_bytes"] = nbytes
    run.counts["moe_held_rows"] = float(rows.sum())
    run.counts["moe_assignments"] = float(
        tokens * n["k"] * expert_layers * steps)
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
    mlps = [layer.mlp for layer in model.model.layers
            if layer.is_expert_layer]

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
    run.check("held_rows_drift",
              rows_drift(first, last,
                         batch * seq * work.routed_rows_per_token(cfg)),
              run.config["limits"]["held_rows_drift"])
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    run.check("compiles_in_window", len(compiles), 0)
    run.check("failed_steps", run.failed, 0)
    run.read_memory_peak()
    # the program's state goes before the reference comes
    del step, opt, model, mlps, feed, loader, loss
    gc.collect()
    from benchmarks.reference import deepseek_v3 as reference

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
            ("fault_routed_experts_left_out", {"drop_held": True}),
            ("fault_rotary_score_left_out", {"drop_rope": True}))


def control_readings(config, seed, fed, what):
    """The reference put in the program's place with one control or fault
    planted: what `compare` is then given as `got`."""
    from benchmarks.reference import deepseek_v3 as reference

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
        raise SystemExit("train_deepseek_v3: a control or a fault came out "
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
