"""The Granite 4.0-H configuration's benchmark files on the CPU at a tiny
size: the configuration against the catalog row's widths, the leaf list
against the model's creation order, the parameter count, the runner's
control flow with the program against the plain reference (each step's
loss, every leaf's first gradient, every leaf's change after three AdamW
steps), each control and planted fault coming out not correct (this
model's own, the recurrence left out, among them), the arithmetic of
work_granite_hybrid.py against hand-worked values, and the new kernel
files' patterns against instruction texts.
"""
import json
import os
import re
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R                        # noqa: E402
from benchmarks import run as run_mod                     # noqa: E402
from benchmarks import weights_granite_hybrid as W        # noqa: E402
from benchmarks import work_granite_hybrid as work        # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "granite4h.pretrain-8k"
CONFIG = "granite-4.0-h-micro-train1"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
ms = 1e-3


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def published():
    return run_mod.load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def tiny(dtype="float32"):
    """Two Mamba layers around one attention layer at toy widths, float32
    (the program and the reference then agree to rounding; what bf16 does
    at the published widths is the chip's business)."""
    cfg = published()
    cfg.update(hidden_size=32, shared_intermediate_size=64,
               intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=8,
               mamba_d_state=16, mamba_chunk_size=8, vocab_size=256,
               num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               attention_multiplier=0.25, initializer_range=0.3,
               max_position_embeddings=256, torch_dtype=dtype)
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size and dtype: above what the sound program reads
    # here (1e-7, 2e-6, 1e-5), below every control and fault
    cfg["limits"] = {"loss3_gap": 2e-5, "grad1_worst_leaf_gap": 1e-3,
                     "grad1_median_leaf_gap": 1e-4,
                     "change_worst_leaf_gap": 1e-3}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    traffic.update(seq_len=40, rows=4096)
    cell = {"name": "tiny.granite4h", "config": "tiny",
            "traffic": "pretrain-8k", "chips": 1}
    return cell, cfg, traffic


TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"}]}
SEED = 2**31 + 7


def _run(seed=SEED, trace=0):
    return run_mod.run_cell(TINY_MANIFEST, *tiny(), seed, 1.0, trace,
                            require_chip=False)


@pytest.fixture(scope="module")
def sound():
    """One run of the runner, shared: (line, Run)."""
    mp = pytest.MonkeyPatch()
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(d, "cache"))
        try:
            yield _run()
        finally:
            mp.undo()


# ------------------------------------------------- model against reference
def test_leaf_list_is_the_models_own_names_and_shapes():
    from benchmarks import train_granite_hybrid

    _, cfg, _ = tiny()
    model = train_granite_hybrid.build_model(cfg, 3)
    named = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert named == [(n, s) for n, s, _ in W.leaf_specs(cfg)]
    # x | B | C convolved: 64 + 16 + 16 channels; z | xBC | dt projected
    assert dict(named)["model.layers.0.mamba.conv_weight"] == (4, 96)
    assert dict(named)["model.layers.0.mamba.in_proj.weight"] == (
        32, 64 + 96 + 8)
    assert "lm_head.weight" not in dict(named)            # tied


def test_the_program_agrees_with_the_reference_leaf_by_leaf(sound):
    """Seeded weights, three AdamW steps: each step's loss, every leaf's
    first gradient and every leaf's change, not the worst leaf alone."""
    _, run = sound
    got, ref = run.kept["got"], run.kept["ref"]
    for a, b in zip(got["losses"], ref["losses"]):
        assert a == pytest.approx(b, rel=2e-6)
    assert set(got["grad_norms"]) == {n for n, _, _ in W.leaf_specs(
        run.config)}
    for key, tol in (("grad_norms", 1e-4), ("change_norms", 3e-4)):
        median = statistics.median(ref[key].values())
        for name, want in ref[key].items():
            gap = abs(got[key][name] - want) / max(want, median)
            assert gap < tol, (key, name, got[key][name], want)
    # every leaf took part: no gradient is nought, every leaf moved
    assert min(ref["grad_norms"].values()) > 0
    assert min(ref["change_norms"].values()) > 0


def test_seeded_leaves_have_their_kinds():
    _, cfg, _ = tiny("bfloat16")
    made = W.make_weights(cfg, 5)
    again = W.make_weights(cfg, 5)
    other = W.make_weights(cfg, 6)
    key, std = W.seed_key(5), cfg["initializer_range"]
    for i, (name, shape, kind) in enumerate(W.leaf_specs(cfg)):
        a = np.asarray(made[name].astype("float32"))
        assert a.shape == shape
        np.testing.assert_array_equal(
            a, np.asarray(again[name].astype("float32")))
        one = W.make_leaf(key, index=i, shape=shape, kind=kind, std=std,
                          dtype=made[name].dtype)
        np.testing.assert_array_equal(a, np.asarray(one.astype("float32")))
        if kind == "zeros":
            assert (a == 0).all() and name.endswith("conv_bias")
        elif kind == "ones":
            assert (a == 1).all()
        elif kind == "a_range":
            # A = 1 .. heads, to bf16 rounding of its logarithm
            np.testing.assert_allclose(np.exp(a), np.arange(1, 9), rtol=2e-2)
        else:
            assert (a != np.asarray(other[name].astype("float32"))).any()
            assert abs(a.std() - std) < 0.25 * std


# ----------------------------------------------------------------- the runner
def test_runner_follows_the_control_flow(sound):
    line, run = sound
    json.dumps(line)
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]
    assert {"loss3_gap", "grad1_worst_leaf_gap", "grad1_median_leaf_gap",
            "change_worst_leaf_gap", "compiles_in_window", "failed_steps",
            "fed_rows_differ"} == set(line["checks"])
    assert any(n.startswith("loss1_gap") for n in line["notes"])
    c = run.counts
    assert c["steps"] == line["attempted"]
    assert c["tokens_per_chip"] == 2 * 40 * c["steps"]
    assert c["required_flops"] == pytest.approx(
        work.train_flops_per_token(run.config, 40) * 2 * 40 * c["steps"])
    ops, nbytes = work.scan_work(run.config, 2 * 40 * c["steps"])
    assert (c["ssd_fwd_flops"], c["ssd_fwd_bytes"]) == (ops, nbytes)
    assert (c["ssd_bwd_flops"], c["ssd_bwd_bytes"]) == (2 * ops, 2 * nbytes)


def test_runner_reports_its_metrics_from_its_counts(sound):
    """On the chip the metrics read these counts; here, with peaks
    supplied by hand, the reducers give numbers and none raises."""
    _, run = sound
    r = R.Readings(run.series, run.counts, {"config": run.config,
                                            "chips": 1}, PEAKS)
    assert R.reduce_metric(_spec("train_step.mfu"), r) > 0
    assert R.reduce_metric(_spec("train_step.input_wait_ms"), r) >= 0
    assert R.reduce_metric(_spec("train_tokens_per_s_per_chip"), r) > 0
    # no trace: the three kernel metrics are left out, not raised
    for m in ("kernels.mamba2_ssd_fwd_roofline",
              "kernels.mamba2_ssd_bwd_roofline",
              "kernels.mamba2_ssd.time_share"):
        assert R.reduce_metric(_spec(m), r) is None


@pytest.mark.parametrize("what", ["control_fp8", "fault_unchanged_state",
                                  "fault_half_batch",
                                  "fault_recurrence_left_out"])
def test_each_control_and_fault_comes_out_not_correct(sound, what):
    """The reference in the program's place with one control or fault
    planted, judged by the harness's own comparison as
    `train_granite_hybrid.py limits` judges it on the chip."""
    from benchmarks import train_granite_hybrid as T

    _, run = sound
    cell, cfg, traffic = tiny()
    assert what in dict(T.CONTROLS)
    got = T.control_readings(cfg, SEED, run.kept["fed"], what)
    judged = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    T.compare(judged, got, run.kept["ref"], cfg["limits"])
    assert not judged.correct(), judged.checks
    same = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    T.compare(same, run.kept["ref"], run.kept["ref"], cfg["limits"])
    assert same.correct()


def test_the_learning_rate_warms_up_as_the_configuration_says():
    """Step t runs at learning_rate * t / warmup_steps, in the reference
    as in the runner (which the leaf-by-leaf comparison holds to it)."""
    from benchmarks import train
    from benchmarks.reference import granite_hybrid as reference

    _, cfg, traffic = tiny()
    opt = cfg["train"]["optimizer"]
    assert (opt["learning_rate"], opt["warmup_steps"]) == (3e-4, 2000)
    fed = train.followed_batches(cfg, traffic, SEED)[:2]
    warm = reference.train_steps(cfg, SEED, fed, opt, dtype="float32")
    flat = reference.train_steps(
        cfg, SEED, fed, dict(opt, warmup_steps=0), dtype="float32")
    name = "model.layers.0.shared_mlp.input_linear.weight"
    # two steps at 1/2000 and 2/2000 of the rate against two at the rate
    ratio = warm["change_norms"][name] / flat["change_norms"][name]
    assert 1 / 2000 < ratio < 2 / 2000
    assert warm["grad_norms"] == flat["grad_norms"]


def test_a_program_that_leaves_its_recurrence_out_is_not_correct(
        monkeypatch):
    """This model's own fault planted in the program: y = D x."""
    from paddle_tpu.ops.impl import ssm_ops

    def only_d(x, dt, a, b, c, d, **kwargs):
        import jax.numpy as jnp

        return (x * jnp.repeat(d, x.shape[-1] // d.shape[0])).astype(x.dtype)

    monkeypatch.setattr(ssm_ops, "mamba2_ssd", only_d)
    line, _ = _run()
    assert not line["correct"], line["checks"]


def test_limits_entry_point_is_this_runners_own():
    """prove.py sends every runner not called `train` down the serving
    branch; the configuration's runner has its own `limits`."""
    from benchmarks import train_granite_hybrid as T

    assert published()["runner"] == "train_granite_hybrid"
    assert [w for w, _ in T.CONTROLS] == [
        "control_fp8", "fault_unchanged_state", "fault_half_batch",
        "fault_recurrence_left_out"]
    assert callable(T.limits) and callable(T.main)


# ------------------------------------------------------- the configuration
def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


# the catalog row's `config`, copied here so that the test holds where the
# catalog is not installed (where it is, the two are compared)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}


def test_the_copy_of_the_catalog_row_is_the_catalog_row():
    row = _catalog_row()
    if row is None:
        pytest.skip("the catalog is not installed here")
    assert row["config"] == CATALOG
    assert row["source_url"] == published()["source"]


def test_configuration_holds_the_sources_keys_and_states_its_cut():
    cfg = published()
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (
        10, 100352 // 8)
    # one whole period of the published pattern, the ratio 9 : 1
    assert cfg["layer_types"] == CATALOG["layer_types"][:10]
    assert W.layer_kinds(cfg) == (9, 1)
    assert CATALOG["layer_types"] == cfg["layer_types"] * 4
    # no width is cut
    for key in ("hidden_size", "shared_intermediate_size", "mamba_d_head",
                "mamba_d_state", "mamba_n_heads", "mamba_expand",
                "num_attention_heads", "num_key_value_heads"):
        assert key not in cfg["reduced"] and cfg[key] == CATALOG[key]
    for key in ("weights", "initializer_range", "conv_weight_layout",
                "parameter_dtype", "time_step_limit", "optimizer",
                "batch_per_replica"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"loss3_gap", "grad1_worst_leaf_gap",
                                  "grad1_median_leaf_gap",
                                  "change_worst_leaf_gap"}
    assert cfg["limits_from"] and cfg["stands_for"]
    assert cfg["pipeline_parallel"] == {"stages": 4, "stage": 0}
    assert cfg["vocab_parallel"] == {"slices": 8}
    tr = cfg["train"]
    assert tr["batch_per_replica"] in (2, 1) and tr["recompute"] is True
    assert tr["fused_loss_chunk"] == 2048
    assert tr["optimizer"]["warmup_steps"] == 2000
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    assert (traffic["seq_len"], traffic["rows"], traffic["warmup_steps"],
            traffic["followed_steps"], traffic["loader_workers"]) == (
                8192, 65536, 4, 3, 2)


def test_parameter_count_is_the_issues_arithmetic():
    cfg = published()
    sizes = {n: int(np.prod(s)) for n, s, _ in W.leaf_specs(cfg)}
    mixer = sum(v for n, v in sizes.items()
                if n.startswith("model.layers.0.mamba."))
    assert mixer == 25_847_232
    assert sum(v for n, v in sizes.items()
               if n.startswith("model.layers.0.")) == 76_182_976
    assert sum(v for n, v in sizes.items()
               if n.startswith("model.layers.5.")) == 60_821_504
    assert sizes["model.embed_tokens.weight"] == 25_690_112
    assert sum(sizes.values()) == 772_160_448
    # 14 B a parameter resident: 10.81 GB of the chip's 16.91
    assert 14 * sum(sizes.values()) == pytest.approx(10.81e9, rel=1e-3)


# ------------------------------------------------ required work, hand-worked
def test_matmul_params_hand_worked():
    cfg = published()
    # in_proj 2048 x (4096 + 4352 + 64), out_proj 4096 x 2048
    assert work.mamba_matmul_params(cfg) == 2048 * 8512 + 4096 * 2048
    # q and o 2048 x 2048, k and v 2048 x 512
    assert work.attention_matmul_params(cfg) == (
        2 * 2048 * 2048 + 2 * 2048 * 512)
    # [a | b] 2048 x 16384, out 8192 x 2048
    assert work.mlp_matmul_params(cfg) == 2048 * 16384 + 8192 * 2048
    assert work.layer_kinds(cfg) == (9, 1)
    assert work.scan_flops_per_token(cfg) == 64 * 4 * 128 * 64
    assert work.conv_flops_per_token(cfg) == 2 * 4 * 4352


def test_training_flops_per_token_hand_worked():
    cfg = published()
    matmuls = 2 * (9 * 25_821_184 + 5_242_880 * 2 + 10 * 50_331_648
                   + 2048 * 12544)
    attention = 4 * 32 * 64 * 8193 / 2
    scan = 9 * (64 * 4 * 128 * 64 + 2 * 4 * 4352)
    fwd = matmuls + attention + scan
    assert work.forward_flops_per_token(cfg, 8192) == pytest.approx(fwd)
    assert work.train_flops_per_token(cfg, 8192) == pytest.approx(3 * fwd)
    # 4.79 GFLOP a token (the issue: ~4.9), 78.5 TFLOP a step of 16,384
    assert 3 * fwd == pytest.approx(4.79e9, rel=2e-3)
    assert 3 * fwd * 16384 == pytest.approx(78.5e12, rel=2e-3)
    # the nine Mamba layers are ~90 % of the layers' work at 8k
    mamba = 9 * (2 * (25_821_184 + 50_331_648) + scan / 9)
    layers = fwd - 2 * 2048 * 12544
    assert 0.88 < mamba / layers < 0.93


def test_kernel_work_hand_worked():
    cfg = published()
    ops, nbytes = work.scan_work(cfg, 1000)
    assert ops == 9 * 1000 * 64 * 4 * 128 * 64
    # a token and layer: x in and y out (4096 bf16 each), B and C (128
    # bf16 each), dt (64 float32): 17,152 B
    assert nbytes == 9 * 1000 * (2 * 2 * 4096 + 2 * 2 * 128 + 4 * 64)
    assert nbytes / 9000 == 17152
    assert work.scan_work(cfg, 1000, backward=True) == (2 * ops, 2 * nbytes)
    # memory-bound on a v5e: the bytes take twice the operations' time
    assert nbytes / PEAKS["hbm_bytes_per_s"] > 1.9 * ops / PEAKS["bf16_flops"]


# ------------------------------------------- kernel files against instructions
def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _call(name, operands="%bitcast.4"):
    return (f"%{name} = bf16[2,8192,4096]{{2,1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


def _trace():
    """A step of 100 ms, busy throughout: the scan forward 2 x 4 ms (the
    layer is rematerialised), backward 12 ms, a flash kernel, and a
    bystander that names a kernel among its operands."""
    ops = [
        (0 * ms, 4 * ms, _call("mamba2_ssd_fwd.9"), ""),
        (4 * ms, 10 * ms, _call("flash_attention_fwd.2"), ""),
        (10 * ms, 11 * ms, "%fusion.7 = bf16[2,8192,4096] fusion(bf16[2,8192,"
         "4096] %mamba2_ssd_fwd.9), kind=kLoop", ""),
        (11 * ms, 15 * ms, _call("checkpoint_mamba2_ssd_fwd.3"), ""),
        (15 * ms, 27 * ms, _call("transpose_jvp_mamba2_ssd_bwd_.1"), ""),
        (27 * ms, 28 * ms, "%convert.3 = f32[2,8192,128] convert("
         "f32[2,8192,128] %mamba2_ssd_bwd.12)", ""),
        (28 * ms, 100 * ms, "%convert_select_fusion.5 = bf16[8192,12288] "
         "fusion(bf16[8192] %copy-done.9), kind=kOutput", ""),
    ]
    lines = {0: {"ops": ops, "modules": [(0.0, 100 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": published()},
                      PEAKS, trace=trace)


WORK = {"ssd_fwd_flops": 197e12 * 1e-3, "ssd_fwd_bytes": 819e9 * 2e-3,
        "ssd_bwd_flops": 197e12 * 2e-3, "ssd_bwd_bytes": 819e9 * 4e-3}


def test_new_kernels_are_found_by_their_own_names():
    r = _readings(_trace(), **WORK)
    value = lambda m: R.reduce_metric(_spec(m), r)
    assert value("kernels.mamba2_ssd.time_share") == pytest.approx(
        4 + 4 + 12)
    # memory-bound (2 ms of bytes against 1 of operations), over the
    # forward's two calls; the backward likewise
    assert value("kernels.mamba2_ssd_fwd_roofline") == pytest.approx(
        100 * 2 / 8)
    assert value("kernels.mamba2_ssd_bwd_roofline") == pytest.approx(
        100 * 4 / 12)


@pytest.mark.parametrize("kernel,matches,not_matches", [
    ("mamba2_ssd_fwd",
     ["%mamba2_ssd_fwd.9 = ", "%jvp_mamba2_ssd_fwd_.1 = "],
     ["%mamba2_ssd_bwd.2 = ",
      "%fusion.7 = f32[1] fusion(%mamba2_ssd_fwd.9)"]),
    ("mamba2_ssd_bwd",
     ["%mamba2_ssd_bwd.2 = ", "%mamba2_ssd_bwd_states.4 = "],
     ["%mamba2_ssd_fwd.9 = "]),
    ("mamba2_ssd",
     ["%mamba2_ssd_fwd.9 = ", "%mamba2_ssd_bwd.2 = "],
     ["%convert.1 = f32[2] convert(%mamba2_ssd_bwd.2)",
      "%gated_delta_rule_fwd.1 = "]),
])
def test_kernel_patterns_are_anchored_at_the_instruction(kernel, matches,
                                                         not_matches):
    k = R._kernel(kernel)
    assert re.search(k.PROGRAMS, "jit_staged")
    for text in matches:
        assert re.search(k.OPS, text), text
    for text in not_matches:
        assert not re.search(k.OPS, text), text


def test_a_program_without_the_kernels_reads_nothing():
    """The parent commit has no such kernels and records no such counts:
    every new metric is left out of its line, none raises."""
    ops = [(0.0, 50 * ms, "%fusion.1 = bf16[8] fusion(%p.1)", "")]
    bare = R.summarize_events(
        {0: {"ops": ops, "modules": [(0.0, 50 * ms, "jit_staged")]}}, [],
        set())
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if m.get("workloads") == [CELL]]
    for readings in (_readings(bare, **WORK), _readings()):
        for name in mine:
            assert R.reduce_metric(_spec(name), readings) is None, name
    # the kernels without the counts: the shares of a roofline are left
    # out, the time share needs no count
    for name in mine:
        value = R.reduce_metric(_spec(name), _readings(_trace()))
        assert (value is None) == ("roofline" in name), name


def test_new_metrics_have_their_entries_and_files():
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "kernels.mamba2_ssd_fwd_roofline", "kernels.mamba2_ssd_bwd_roofline",
        "kernels.mamba2_ssd.time_share"]
    for m in mine:
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert (m["unit"], m["source"], m["layer"]) == (
            "%", "device_trace", "kernels")
        assert set(_spec(m["name"])) == {"reducer", "args", "reads"}
    assert [m["better"] for m in mine] == ["higher", "higher", "lower"]
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-8k", 1)
    assert MANIFEST["workloads"][-1] is cell and len(cell["why"]) <= 200
    entry = MANIFEST["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == published()[
        "reduced"]
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run_mod.cell_metrics(MANIFEST, cell, g)}
    assert reported == {
        "train_tokens_per_s_per_chip", "setup_s", "train_step.mfu",
        "train_step.input_wait_ms", "kernels.mamba2_ssd_fwd_roofline",
        "kernels.mamba2_ssd_bwd_roofline", "kernels.mamba2_ssd.time_share"}
    # the cell's name stands last in the lists it was appended to
    for group, name in (("end_to_end", "train_tokens_per_s_per_chip"),
                        ("per_layer", "train_step.mfu"),
                        ("per_layer", "train_step.input_wait_ms")):
        entry = next(m for m in MANIFEST[group] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
