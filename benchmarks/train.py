"""The training runner: jit.TrainStep over the forked-worker DataLoader.

Set-up builds one object, the compiled step with its state, drives it from
the seed through the traffic file's warm-up steps and hands the same object
to the window. The first `followed_steps` of those are what `correct`
compares with the plain reference once the window has closed: each step's
loss, the norm of the first gradient as the optimizer got it (Adam's first
moment after one step, over 1 - beta1) and the norm of the parameters'
change after the followed steps, both by the worst leaf.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmarks import weights as W
from benchmarks import work
from benchmarks.run import span

SPANS = ("train.step", "data.next")


class SeededRows:
    """Map-style dataset: row i is a function of (seed, i), so every row
    differs and the forked workers need no shared state."""

    def __init__(self, seed, rows, seq_len, vocab):
        self.seed, self.rows, self.seq_len, self.vocab = (
            seed, rows, seq_len, vocab)

    def __len__(self):
        return self.rows

    def __getitem__(self, i):
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.vocab, self.seq_len).astype("int32")


def model_config(cfg, **extra):
    """LlamaConfig arguments from the source's keys."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return {**{k: cfg[k] for k in keys}, **extra}


def build_model(cfg, seed, **extra):
    """The program's model holding the seed's weights: every parameter is
    created from the array benchmarks/weights.py made for it, so no second
    copy of the model ever exists."""
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn import initializer as I

    made = W.make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    queue = list(made.items())

    def hand_out(shape, dtype=None):
        name, array = queue.pop(0)
        if tuple(shape) != array.shape:
            raise RuntimeError(
                f"weights.leaf_specs is out of step with the model: {name} "
                f"is {array.shape}, the model asked for {tuple(shape)}")
        return array

    with I.param_init_override(hand_out, dtype=cfg["torch_dtype"]):
        model = LlamaForCausalLM(LlamaConfig(**model_config(cfg, **extra)))
    if queue:
        raise RuntimeError(f"{len(queue)} leaves were never asked for")
    return model


def followed_batches(cfg, traffic, seed):
    """The batches of the followed steps as the benchmark itself builds
    them from SeededRows: the loader feeds rows in order, a batch a step.
    The reference follows these, whatever the loader delivered."""
    rows = SeededRows(seed, traffic["rows"], traffic["seq_len"],
                      cfg["vocab_size"])
    b = cfg["train"]["batch_per_replica"]
    return [np.stack([rows[s * b + i] for i in range(b)])
            for s in range(traffic["followed_steps"])]


def rows_that_differ(fed, expected):
    """How many rows the loader delivered other than the seeded ones (a
    batch of another shape counts whole)."""
    return sum(len(e) if f.shape != e.shape
               else int((f != e).any(axis=1).sum())
               for f, e in zip(fed, expected))


def _leaf_readings(opt, model, cfg, seed, beta1, want):
    """Per-leaf norms read from the optimizer's checkpoint state
    (`state_dict`, keyed `<parameter name>_<accumulator>_0`): `grad` from
    the first moment after step 1, `change` from the master weights
    against the seed's own leaves."""
    import jax
    import jax.numpy as jnp

    state = opt.state_dict()
    acc = "moment1" if want == "grad" else "master_weight"
    arrays = [state[f"{p.name}_{acc}_0"]._data for p in model.parameters()]
    del state
    names = [n for n, _ in W.leaf_specs(cfg)]
    if want == "grad":
        fn = jax.jit(lambda ms: [
            jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32))))
            / (1 - beta1) for m in ms])
        return {n: float(v) for n, v in zip(names, fn(arrays))}
    key = W.seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out = {}
    for i, (n, shape) in enumerate(W.leaf_specs(cfg)):
        first = W.make_leaf(key, index=i, shape=shape, std=std,
                            dtype=jnp.dtype(cfg["torch_dtype"]))
        out[n] = float(diff(arrays[i], first))
    return out


def worst_leaf_gap(got, ref, leave_out=()):
    """The widest gap between the program's norm and the reference's over
    the leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    median = statistics.median(ref.values())
    return max(abs(got[n] - ref[n]) / max(ref[n], median)
               for n in ref if n not in leave_out)


def compare(run, got, ref, limits):
    """The numbers `correct` is decided on, each beside its limit. A
    number the configuration gives no limit (PERF.md says why: no control
    or fault reads above what sound runs read) is kept as a note."""
    values = {f"loss{i}_gap": abs(a - b) / abs(b) for i, (a, b) in
              enumerate(zip(got["losses"], ref["losses"]), 1)}
    values["grad1_worst_leaf_gap"] = worst_leaf_gap(
        got["grad_norms"], ref["grad_norms"])
    # a leaf whose gradient is nought to rounding moves under Adam by
    # round-off alone: left out by a rule on the reference's gradient
    median = statistics.median(ref["grad_norms"].values())
    still = [n for n, g in ref["grad_norms"].items() if g < 1e-3 * median]
    values["change_worst_leaf_gap"] = worst_leaf_gap(
        got["change_norms"], ref["change_norms"], still)
    for name, value in values.items():
        if name in limits:
            run.check(name, value, limits[name])
        else:
            run.notes.append(f"{name} {value:.6g} (not compared)")
    return values


def run(run):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.compilecache import enable_persistent_cache
    from paddle_tpu.observability import jit_events

    cfg, tr, t = run.config, run.config["train"], run.traffic
    enable_persistent_cache()
    model = build_model(cfg, run.seed, fused_loss_chunk=tr["fused_loss_chunk"])
    batch = tr["batch_per_replica"]
    o = tr["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
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
    tokens = batch * seq
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
    steps, waits = run.series["step_ms"], run.series["input_wait_ms"]
    # where a window's seconds went, so that a run that reads low says why
    run.notes.append(
        f"window {now - opened:.3f}s = steps {sum(steps) / 1e3:.3f}s + input "
        f"wait {sum(waits) / 1e3:.3f}s + rest; steps: median "
        f"{statistics.median(steps):.1f} ms, slowest {max(steps):.1f} ms "
        f"(step {steps.index(max(steps)) + 1}); input wait: slowest "
        f"{max(waits):.1f} ms (step {waits.index(max(waits)) + 1})")
    run.counts["steps"] = run.attempted
    run.counts["tokens_per_chip"] = (
        tokens * run.attempted / run.cell["chips"])
    run.counts["required_flops"] = (
        work.train_flops_per_token(cfg, seq) * tokens * run.attempted)
    # what the flash-attention calls are asked for
    run.counts["flash_sequences"] = batch * run.attempted
    run.counts["flash_seq_len"] = seq
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    run.check("compiles_in_window", len(compiles), 0)
    run.check("failed_steps", run.failed, 0)
    run.read_memory_peak()
    # the program's state goes before the reference comes
    del step, opt, model, feed, loader, loss
    gc.collect()
    from benchmarks.reference import decoder

    expected = followed_batches(cfg, t, run.seed)
    run.check("fed_rows_differ", rows_that_differ(fed, expected), 0)
    ref = decoder.train_steps(cfg, run.seed, expected, o)
    compare(run, got, ref, run.config["limits"])
    run.kept.update(fed=expected, got=got, ref=ref)
    run.notes.append(f"losses {got['losses']} reference {ref['losses']}")
