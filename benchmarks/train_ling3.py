"""The training runner of the Ling 3.0 configurations (Kimi Delta Attention
and MLA over a leading dense layer and expert layers behind a group-limited
sigmoid router): train.run's control flow (set-up, the traffic file's
warm-up steps of which the first are followed, the window, exact-count
checks, the reference once the program's state is freed) around
models/ling3.py, with leaves, reference and required work from
weights_ling3.py, reference/ling3.py and work_ling3.py. What of the other
runners can be imported is: the seeded rows, the followed batches, the
fed-rows count and the spans (train.py); the comparison with its median
leaf (train_hybrid.py); the held rows' drift between the window's halves
and the router's notes (train_mellum2.py). `build_model`,
`_leaf_readings`, the window loop and `limits` repeat train_mellum2.py's
over this model's leaves: those are bound to their own model's modules,
and an accepted runner is not edited to take them by parameter. Of its
own: the KDA kernels' work, and the tokens whose kept groups include the
held experts' group (each expert layer's `group_load`, added up on the
device after every step).

    python3 benchmarks/train_ling3.py limits --workload <cell> \\
        --seeds 1,2,3 [--control 3] [--manifest <draft.json>]

is prove.py's `limits` for this runner: the program on every seed, then
for the first `--control` seeds the fp8 control and the planted faults
(state unchanged, half the batch, and this model's own: each head's decay
its channels' mean, the group limit dropped, the held experts left out),
each judged by prove._judged and each to come out not correct.
"""
from __future__ import annotations

import gc
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import weights_ling3 as W               # noqa: E402
from benchmarks import work_ling3 as work               # noqa: E402
from benchmarks.run import span                         # noqa: E402
from benchmarks.train import (SPANS, SeededRows,        # noqa: E402
                              followed_batches, rows_that_differ)
from benchmarks.train_hybrid import compare              # noqa: E402
from benchmarks.train_mellum2 import (                   # noqa: E402
    held_rows_drift, router_notes)

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "head_dim", "kv_lora_rank",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rope_interleave", "rope_scaling", "rms_norm_eps",
    "first_k_dense_replace", "layer_group_size", "num_experts_per_tok",
    "num_shared_experts", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "score_function", "use_qk_norm",
    "short_conv_kernel_size", "kda_lower_bound", "kda_safe_gate",
    "no_kda_lora", "gated_attention_proj_granularity_type",
    "max_position_embeddings", "initializer_range")


def model_config(cfg, **extra):
    """Ling3Config arguments from the source's keys: the router keeps its
    published width, `num_experts` are held here, `kept_layers` names the
    published layers this chip holds."""
    n = W.dims(cfg)
    return {**{k: cfg[k] for k in MODEL_KEYS}, "num_experts": n["experts"],
            "held_experts": (n["held_start"], n["held"]),
            "layer_ids": tuple(W.layer_ids(cfg)), **extra}


def build_model(cfg, seed, **extra):
    """The program's model holding the seed's weights: every parameter is
    created from the array weights_ling3 made for it, so no second copy
    of the model ever exists."""
    import jax.numpy as jnp

    from paddle_tpu.models import Ling3Config, Ling3ForCausalLM
    from paddle_tpu.nn import initializer as I

    made = W.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    queue = list(made.items())

    def hand_out(shape, dtype=None):
        name, array = queue.pop(0)
        if tuple(shape) != array.shape:
            raise RuntimeError(
                f"weights_ling3.leaf_specs is out of step with the model: "
                f"{name} is {array.shape}, the model asked for "
                f"{tuple(shape)}")
        return array

    with I.param_init_override(hand_out, dtype=cfg["torch_dtype"]):
        model = Ling3ForCausalLM(Ling3Config(**model_config(cfg, **extra)))
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


def held_group(cfg):
    """The group the held experts lie in (they lie in one)."""
    n = W.dims(cfg)
    per = n["experts"] // n["groups"]
    group = n["held_start"] // per
    if (n["held_start"] + n["held"] - 1) // per != group:
        raise ValueError("train_ling3: the held experts span two groups")
    return group


def record_work(run, cfg, seq, batch, rows, imbalance, group_tokens):
    """The counts the per-layer metrics read: required operations, the KDA
    kernels' work, the expert layers' `expert_load` summed over the
    window's steps (`rows` [expert layers, held], `imbalance` [expert
    layers], each step's largest held expert's rows over the mean, summed)
    and the tokens whose kept groups include the held experts' group,
    summed over the expert layers and the window's steps."""
    tokens = batch * seq
    steps = run.attempted
    n = W.dims(cfg)
    _, _, _, expert_layers = work.layer_kinds(cfg)
    run.counts["steps"] = steps
    run.counts["tokens_per_chip"] = tokens * steps / run.cell["chips"]
    run.counts["required_flops"] = (
        work.train_flops_per_token(cfg, seq) * tokens * steps)
    rows = np.asarray(rows, np.float64)
    for side, backward in (("fwd", False), ("bwd", True)):
        ops, nbytes = work.kda_core_work(cfg, batch * steps, seq, backward)
        run.counts[f"kda_{side}_flops"] = ops
        run.counts[f"kda_{side}_bytes"] = nbytes
        ops, nbytes = work.grouped_matmul_work(
            cfg, rows.sum(), steps * expert_layers, backward)
        run.counts[f"gmm_{side}_flops"] = ops
        run.counts[f"gmm_{side}_bytes"] = nbytes
    run.counts["moe_held_rows"] = float(rows.sum())
    run.counts["moe_assignments"] = float(
        tokens * n["k"] * expert_layers * steps)
    run.counts["moe_load_max_over_mean"] = float(
        np.max(np.asarray(imbalance, np.float64)) / max(steps, 1))
    run.counts["moe_held_group_tokens"] = float(group_tokens)
    run.counts["moe_routed_tokens"] = float(tokens * expert_layers * steps)


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
    group = held_group(cfg)

    @jax.jit
    def add_loads(sums, loads, group_loads):
        """The step's `expert_load` and `group_load` buffers added to the
        window's sums on the device: nothing of it is read before the
        window has closed."""
        loads = jnp.stack(loads)
        mean = jnp.maximum(loads.mean(axis=1, dtype=jnp.float32), 1e-9)
        held = jnp.stack(group_loads)[:, group].sum(dtype=jnp.float32)
        return (sums[0] + loads, sums[1] + loads.max(axis=1) / mean,
                sums[2] + held, loads)

    def no_loads():
        return (jnp.zeros((len(mlps), mlps[0].held[1]), jnp.int32),
                jnp.zeros((len(mlps),), jnp.float32),
                jnp.zeros((), jnp.float32), None)

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
        sums = add_loads(sums[:3], [m.expert_load._data for m in mlps],
                         [m.group_load._data for m in mlps])
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
    first = np.asarray(sums[3]).sum(axis=1)
    sums = no_loads()
    routers = [m.gate.weight for m in mlps]
    w_first = [np.asarray(w._data).astype(np.float32) for w in routers]
    seen = []       # each window step's expert_load, read after the window
    jit_events.clear_compile_log()
    run.span_names = SPANS
    opened = now = run.open_window()
    while now - opened < run.seconds:
        _, loss, wait, wall = one_step()
        seen.append(sums[3])
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
    record_work(run, cfg, seq, batch, sums[0], sums[1], float(sums[2]))
    last = first if sums[3] is None else np.asarray(sums[3]).sum(axis=1)
    series = np.stack([np.asarray(x) for x in seen]) if seen else None
    run.notes.append(
        f"expert_load, rows a layer: {[int(x) for x in first]} after the "
        f"warm-up steps, {[int(x) for x in last]} after the last step, in "
        f"passes of {mlps[0].held_rows(batch * seq)}; tokens keeping the "
        f"held group {group}, over the window: {float(sums[2]):.0f}")
    if seen:
        run.notes.extend(router_notes(
            series, w_first,
            [np.asarray(w._data).astype(np.float32) for w in routers]))
    # the cell's traffic is the router's load: a router that leaves the
    # held experts inside the window is another cell
    run.check("held_rows_drift",
              0.0 if series is None else held_rows_drift(series.sum(axis=2)),
              run.config["limits"]["held_rows_drift"])
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    run.check("compiles_in_window", len(compiles), 0)
    run.check("failed_steps", run.failed, 0)
    run.read_memory_peak()
    # the program's state goes before the reference comes
    del step, opt, model, mlps, routers, seen, feed, loader, loss
    gc.collect()
    from benchmarks.reference import ling3 as reference

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
            ("fault_decay_head_mean", {"mean_decay": True}),
            ("fault_group_limit_dropped", {"no_group_limit": True}),
            ("fault_held_experts_left_out", {"drop_held": True}))


def control_readings(config, seed, fed, what):
    """The reference put in the program's place with one control or fault
    planted: what `compare` is then given as `got`."""
    from benchmarks.reference import ling3 as reference

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
        raise SystemExit("train_ling3: a control or a fault came out "
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
