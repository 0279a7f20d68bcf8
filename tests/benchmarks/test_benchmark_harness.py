"""The benchmark's harness on the CPU: its arithmetic against hand-worked
values, its data files against BENCHMARK.json, and its runners at a tiny
size, where they follow the control flow of a chip run but may report no
device metric. The controls and the planted faults of `correct` live here
too: each has to come out as not correct.
"""
import copy
import io
import json
import os
import re
import sys
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R            # noqa: E402
from benchmarks import run as run_mod         # noqa: E402
from benchmarks import traffic as TR          # noqa: E402
from benchmarks import work                   # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    """The runners keep their compile caches under compilecache.
    cache_root(); a test's go to its own directory, not the checkout's."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "vocab_size": 32768, "num_hidden_layers": 2}


# ---------------------------------------------------------- trace reduction
def _synthetic():
    """One device: program `jit_decode` runs [0, 4] and [6, 9] ms; in the
    first call a while loop [0, 4] holds a kernel [1, 3]. The host sat in
    engine.step over [0, 5.5] and [5.5, 10] ms."""
    ms = 1e-3
    ops = [(0 * ms, 4 * ms, "while.1", "while"),
           (1 * ms, 3 * ms, "custom-call.7", "tpu_custom_call paged"),
           (6 * ms, 9 * ms, "fusion.2", "fusion")]
    modules = [(0 * ms, 4 * ms, "jit_decode"), (6 * ms, 9 * ms, "jit_decode")]
    spans = [(0 * ms, 5.5 * ms, "engine.step"),
             (5.5 * ms, 10 * ms, "engine.step"),
             (2 * ms, 3 * ms, "not.ours")]
    return {0: {"ops": ops, "modules": modules}}, spans


def test_trace_busy_idle_and_window():
    t = R.summarize_events(*_synthetic(), {"engine.step"})
    assert t.window_s == pytest.approx(10e-3)
    assert t.busy_s == pytest.approx(7e-3)
    assert t.devices == 1


def test_trace_kernel_time_by_pattern_and_self_time():
    t = R.summarize_events(*_synthetic(), {"engine.step"})
    evs = R._matching(t, "decode", "tpu_custom_call")
    assert sum(e - s for s, e in evs) == pytest.approx(2e-3)
    assert R._matching(t, "prefill", "tpu_custom_call") == []
    # the loop keeps only what its child does not cover
    assert t.op_self["jit_decode:while.1"] == pytest.approx(2e-3)
    assert t.op_self["jit_decode:custom-call.7"] == pytest.approx(2e-3)
    assert t.program_calls["jit_decode"] == pytest.approx([4e-3, 3e-3])


def test_trace_gap_attribution_and_host_time():
    t = R.summarize_events(*_synthetic(), {"engine.step"})
    # idle: [4, 6] and [9, 10] ms, all inside engine.step spans
    assert t.idle_gaps == {"engine.step": pytest.approx(3e-3)}
    r = R.Readings({}, {}, {"chips": 1}, {}, trace=t)
    # two spans, 10 ms of wall, 7 ms busy inside them
    assert R.r_span_host_ms(r, "engine.step") == pytest.approx(1.5)
    assert R.breakdown(t)["idle_gaps"] == [["engine.step",
                                            pytest.approx(3e-3)]]


def test_trace_gap_outside_any_span_is_named_so():
    lines, spans = _synthetic()
    t = R.summarize_events(lines, spans[:1], {"engine.step"})
    assert t.idle_gaps[R.NO_SPAN] == pytest.approx(0.5e-3)


def test_quantile_is_exact():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert R.quantile(xs, 0.5) == 3.0
    assert R.quantile(xs, 0.99) == pytest.approx(np.percentile(xs, 99))
    assert R.quantile([], 0.5) is None


def test_a_reducer_with_nothing_to_read_returns_nothing():
    r = R.Readings({}, {}, {"chips": 1, "config": MISTRAL},
                   {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    for spec in ({"reducer": "quantile", "args": {"series": "x", "q": 0.5}},
                 {"reducer": "mfu", "args": {}},
                 {"reducer": "kernel_roofline",
                  "args": {"kernel": "paged_attention"}},
                 {"reducer": "program_ms", "args": {"programs": "decode"}}):
        assert R.reduce_metric(spec, r) is None


# --------------------------------------------------------------- rooflines
def test_training_flops_per_token_hand_worked():
    # one layer: 4096*4096*2 + 2*4096*1024 + 3*4096*14336 weights
    assert work.layer_matmul_params(MISTRAL) == 218_103_808
    # forward, a token: 2 layers x (2 x 218.1M + 2 x 32 x 128 x 4097
    # for causal attention at half the square) + head 2 x 4096 x 32768
    fwd = 2 * (2 * 218_103_808 + 2 * 32 * 128 * 4097) + 2 * 4096 * 32768
    assert work.train_flops_per_token(MISTRAL, 4096) == pytest.approx(
        3 * fwd)
    assert 3 * fwd == pytest.approx(3.62e9, rel=5e-3)


def test_flash_roofline_hand_worked():
    from benchmarks.kernels import flash_attention as k

    # one row of 4096 in 2 layers: forward 4*32*128*4096*4097/2, x3
    flops = 3 * 2 * (4 * 32 * 128 * 4096 * 4097 / 2)
    least = k.least_seconds(
        {"flash_sequences": 1, "flash_seq_len": 4096},
        {"config": MISTRAL}, {"bf16_flops": 197e12})
    assert least == pytest.approx(flops / 197e12)
    assert k.least_seconds({}, {"config": MISTRAL}, {}) is None


def test_paged_roofline_from_live_tokens_hand_worked():
    from benchmarks.kernels import paged_attention as k

    cfg = dict(MISTRAL, num_hidden_layers=16)
    # 16 slots of 1000 live tokens, one step: keys and values of 8 kv
    # heads x 128 in bf16, once; queries and outputs of 32 heads x 128
    nbytes = 16 * 2 * (2 * 8 * 128 * 16000 + 2 * 32 * 128 * 16)
    ops = 16 * 4 * 32 * 128 * 16000
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = k.least_seconds(
        {"decode_live_tokens": 16000, "decode_slot_steps": 16},
        {"config": cfg}, peaks)
    assert least == pytest.approx(max(nbytes / 819e9, ops / 197e12))
    assert least == pytest.approx(nbytes / 819e9)      # memory bound
    # nothing of the kernel's grid or page capacity enters: 304 pages a
    # slot would be 16 x 304 x 16 = 77,824 tokens, five times the bytes
    assert nbytes < 2 * 16 * 2 * 8 * 128 * 77824 / 4


def test_roofline_share_is_a_percentage_under_100():
    lines, spans = _synthetic()
    t = R.summarize_events(lines, spans, {"engine.step"})
    cfg = dict(MISTRAL, num_hidden_layers=16)
    r = R.Readings({}, {"decode_live_tokens": 16000,
                        "decode_slot_steps": 16},
                   {"chips": 1, "config": cfg},
                   {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, t)
    share = R.r_kernel_roofline(r, "paged_attention")
    assert 0 < share < 100
    assert share == pytest.approx(100 * 1.29e-3 / 2e-3, rel=0.01)
    assert R.r_kernel_time_share(r, "paged_attention") == pytest.approx(
        100 * 2 / 7)


# ------------------------------------------------------------------ traffic
def _traffic_files():
    d = os.path.join(ROOT, "benchmarks", "traffic")
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("name", [
    f for f in _traffic_files()
    if "trace_seed" in run_mod.load(ROOT, "benchmarks", "traffic", f)])
def test_trace_is_fixed_by_trace_seed_and_not_by_seed(name):
    spec = run_mod.load(ROOT, "benchmarks", "traffic", name)
    assert TR.trace_bytes(spec) == TR.trace_bytes(copy.deepcopy(spec))
    other = dict(spec, trace_seed=spec["trace_seed"] + 1)
    assert TR.trace_bytes(other) != TR.trace_bytes(spec)
    plan = (TR.open_loop_trace(spec) if spec["kind"] == "open_loop_trace"
            else TR.closed_loop_lists(spec)[0])
    a = TR.prompt_ids(1, plan[0], 32768)
    b = TR.prompt_ids(2**31 + 7, plan[0], 32768)
    assert len(a) == len(b) == plan[0].prompt_len and a != b
    assert a == TR.prompt_ids(1, plan[0], 32768)


def test_open_loop_rate_scales_one_realisation():
    spec = run_mod.load(ROOT, "benchmarks", "traffic", "chat-trace.json")
    one = TR.open_loop_trace(dict(spec, rate_per_s=1.0))
    two = TR.open_loop_trace(dict(spec, rate_per_s=2.0))
    assert [p.prompt_len for p in one] == [p.prompt_len
                                           for p in two[:len(one)]]
    assert two[5].due_s == pytest.approx(one[5].due_s / 2)
    lo, hi = spec["prompt_tokens"]["min"], spec["prompt_tokens"]["max"]
    assert all(lo <= p.prompt_len <= hi for p in one)


@pytest.mark.parametrize("change", [
    {"prompt_tokens": {"dist": "fixed", "value": 8, "min": 1, "max": 9}},
    {"arrivals": {"process": "gamma", "cv": 3}}])
def test_the_generator_refuses_what_it_does_not_know(change):
    """A mix the one generator cannot make is an error, never a silent
    default: the PR that proves such a mix brings the code with it."""
    spec = run_mod.load(ROOT, "benchmarks", "traffic", "chat-trace.json")
    with pytest.raises(ValueError):
        TR.open_loop_trace(dict(spec, **change))


# ------------------------------------------------------------ the manifest
def test_manifest_names_units_and_files():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".json")), m["name"]
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["unit"] == "%":
            assert "roofline" in m["name"] or "mfu" in m["name"].split(
                ".") or "share" in m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_has_its_files(cell):
    w = {x["name"]: x for x in MANIFEST["workloads"]}[cell]
    entry = {c["name"]: c for c in MANIFEST["configs"]}[w["config"]]
    assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    config = run_mod.load(ROOT, entry["file"])
    assert entry["file"].startswith("benchmarks/configs/")
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in config["published"]
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", config["runner"] + ".py"))
    traffic = run_mod.load(ROOT, "benchmarks", "traffic",
                           w["traffic"] + ".json")
    assert traffic["kind"] in ("train_stream", "open_loop_trace",
                               "closed_loop_list")
    reported = {g: run_mod.cell_metrics(MANIFEST, w, g)
                for g in ("end_to_end", "per_layer")}
    assert any(m["name"] == "setup_s" for m in reported["end_to_end"])
    assert len(reported["end_to_end"]) >= 2 and reported["per_layer"]
    for m in reported["per_layer"]:
        spec = run_mod.load(ROOT, "benchmarks", "metrics",
                            m["name"] + ".json")
        assert spec["reducer"] in R.REDUCERS or os.path.isfile(os.path.join(
            ROOT, "benchmarks", "reducers", spec["reducer"] + ".py"))
        kernel = spec.get("args", {}).get("kernel")
        if kernel:
            assert os.path.isfile(os.path.join(
                ROOT, "benchmarks", "kernels", kernel + ".py"))


def _metric_files():
    d = os.path.join(ROOT, "benchmarks", "metrics")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("name", _metric_files())
def test_metric_file_names_a_reducer_that_exists(name):
    """Every metric file, in BENCHMARK.json today or waiting for its
    cell: a legal name, a reducer that is built in or a file, arguments
    the reducer takes, and its kernel's file."""
    import inspect

    assert NAME.match(name)
    spec = run_mod.load(ROOT, "benchmarks", "metrics", name + ".json")
    assert set(spec) == {"reducer", "args", "reads"}
    fn = R.REDUCERS[spec["reducer"]]
    params = list(inspect.signature(fn).parameters)[1:]
    assert set(spec["args"]) <= set(params)
    kernel = spec["args"].get("kernel")
    if kernel:
        k = R._kernel(kernel)
        assert re.compile(k.PROGRAMS) and re.compile(k.OPS)


def test_peaks_table_has_its_source():
    table = run_mod.load(ROOT, "benchmarks", "peaks.json")
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


# ------------------------------------------------- the runners, tiny, on CPU
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=256,
            num_hidden_layers=2, max_position_embeddings=256,
            initializer_range=0.1)
TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"},
                   {"name": "itl_p99_ms", "unit": "ms"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"}]}


def _tiny_train():
    cfg = run_mod.load(ROOT, "benchmarks", "configs",
                       "mistral-7b-v0.3-train1.json")
    cfg.update(TINY)
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size, set as the chip's are: above what the sound
    # program reads here (3e-5, 2e-3, 2e-3), below control and faults
    cfg["limits"] = {"loss1_gap": 5e-4, "loss3_gap": 5e-4,
                     "grad1_worst_leaf_gap": 0.02,
                     "change_worst_leaf_gap": 0.02}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-4k.json")
    traffic.update(seq_len=64, rows=4096)
    cell = {"name": "tiny.pretrain", "config": "tiny",
            "traffic": "pretrain-4k", "chips": 1}
    return cell, cfg, traffic


def _tiny_serve(kind):
    cfg = run_mod.load(ROOT, "benchmarks", "configs",
                       "mistral-7b-v0.3-serve1.json")
    cfg.update(TINY)
    cfg["engine"] = dict(
        cfg["engine"], max_batch_slots=4, max_model_len=128, page_size=8,
        num_blocks=64, prefill_chunk_tokens=16, prefill_buckets=[8, 16, 128])
    cfg["sample"] = {"requests": 4, "served_tokens": 60, "tokens": 600}
    cfg["limits"] = {"served_logit_gap_max": 0.1}
    short = {"dist": "lognormal", "median": 24, "sigma": 1.0, "min": 4,
             "max": 80}
    answer = {"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 3,
              "max": 24}
    if kind == "open":
        traffic = run_mod.load(ROOT, "benchmarks", "traffic",
                               "chat-trace.json")
        traffic.update(rate_per_s=6.0, warmup_s=1.5, horizon_s=30,
                       prompt_tokens=short, output_tokens=answer)
    else:
        traffic = run_mod.load(ROOT, "benchmarks", "traffic",
                               "docqa-closed.json")
        traffic.update(clients=4, warmup_s=1.5, stagger_s=0.2,
                       prompt_tokens=dict(short, min=20), per_client=8,
                       output_tokens=answer)
    cell = {"name": "tiny." + kind, "config": "tiny", "traffic": "x",
            "chips": 1}
    return cell, cfg, traffic


def _run_both(cell, cfg, traffic, seed=3, seconds=1.0, trace=0):
    """The result line and the Run it was read from."""
    return run_mod.run_cell(TINY_MANIFEST, cell, cfg, traffic, seed,
                            seconds, trace, require_chip=False)


def _run(*args, **kw):
    return _run_both(*args, **kw)[0]


LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _check_line(line, trace):
    json.dumps(line)
    assert LINE_KEYS <= set(line) and list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # off the chip no device metric is reported, whatever the manifest asks
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


@pytest.mark.parametrize("trace", [0, 1])
def test_train_runner_follows_the_control_flow(trace):
    line = _run(*_tiny_train(), seed=2**31 + 5, trace=trace)
    _check_line(line, trace)
    assert line["correct"], line["checks"]
    assert {"loss1_gap", "loss3_gap", "grad1_worst_leaf_gap",
            "change_worst_leaf_gap", "compiles_in_window",
            "failed_steps", "fed_rows_differ"} == set(line["checks"])
    assert any(n.startswith("loss2_gap") for n in line["notes"])


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_serve_runner_follows_the_control_flow(kind):
    line, run = _run_both(*_tiny_serve(kind), seed=2**31 + 9, seconds=2.0)
    _check_line(line, 0)
    assert line["correct"], line["checks"]
    assert {"served_logit_gap_max", "compiles_since_warmup",
            "failed_requests", "wrong_length_answers"} == set(line["checks"])
    # the rate's own span: the counted steps, first start to last end
    span, steps = run.counts["counted_steps_s"], run.series["engine_step_ms"]
    assert sum(steps) / 1e3 <= span * 1.001 and span < 2.0 + max(steps) / 1e3
    spec = run_mod.load(ROOT, "benchmarks", "metrics",
                        "serve_tokens_per_s.json")
    r = R.Readings(run.series, run.counts, {}, {})
    assert R.reduce_metric(spec, r) == pytest.approx(
        run.counts["generated_tokens"] / span)


def test_measuring_path_refuses_off_the_chip(capsys):
    cell = MANIFEST["workloads"][0]["name"]
    with pytest.raises(SystemExit) as e:
        run_mod.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                      "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_unknown_cell_is_an_error():
    with pytest.raises(SystemExit):
        run_mod.main(["--workload", "no.such.cell", "--seed", "1",
                      "--seconds", "1"])


def test_main_prints_checks_last_on_stderr(monkeypatch):
    cell, cfg, traffic = _tiny_train()
    real = run_mod.run_cell
    monkeypatch.setattr(run_mod, "run_cell", lambda *a, **k: real(
        TINY_MANIFEST, cell, cfg, traffic, 3, 1.0, 0, require_chip=False))
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run_mod.main(["--workload", MANIFEST["workloads"][0]["name"],
                      "--seed", "1", "--seconds", "1"])
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert LINE_KEYS <= set(last)
    tail = err.getvalue().strip().splitlines()
    assert tail[-1] == f"correct: {last['correct']}"
    assert sum(l.startswith("check ") for l in tail) == len(last["checks"])


# ----------------------------------------- the control and the planted faults
def _train_readings(cfg, traffic, seed, **kw):
    from benchmarks import train
    from benchmarks.reference import decoder

    fed = train.followed_batches(cfg, traffic, seed)
    return decoder.train_steps(cfg, seed, fed, cfg["train"]["optimizer"],
                               **kw)


@pytest.mark.parametrize("what", ["control_fp8", "fault_half_batch"])
def test_training_control_and_fault_come_out_not_correct(what):
    """The reference in the program's place, in the next precision below
    bf16 or with half of the batch left out, fails a limit that the sound
    program keeps."""
    from benchmarks import train

    _, cfg, traffic = _tiny_train()
    ref = _train_readings(cfg, traffic, 5)
    kw = {"mode": "fp8"} if what == "control_fp8" else {"half_batch": True}
    got = _train_readings(cfg, traffic, 5, **kw)
    # through the harness's own comparison, as benchmarks/prove.py does
    # on the chip: the verdict is Run.correct()
    cell = {"name": "tiny.pretrain", "chips": 1}
    sound = run_mod.Run(cell, cfg, traffic, 5, 0.0, False)
    train.compare(sound, ref, ref, cfg["limits"])
    assert sound.correct()
    run = run_mod.Run(cell, cfg, traffic, 5, 0.0, False)
    train.compare(run, got, ref, cfg["limits"])
    assert not run.correct(), run.checks


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    import paddle_tpu as paddle

    def lazy_step(self, *args, **kwargs):
        for p in self._params:
            self._opt._ensure_state(p)
        with paddle.no_grad():
            return self._loss_fn(self._model, *args, **kwargs)

    monkeypatch.setattr(paddle.jit.TrainStep, "__call__", lazy_step)
    line = _run(*_tiny_train())
    assert not line["correct"]
    assert line["checks"]["change_worst_leaf_gap"]["value"] == pytest.approx(
        1.0)
    assert line["checks"]["grad1_worst_leaf_gap"]["value"] == pytest.approx(
        1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from paddle_tpu.models import LlamaForCausalLM

    whole = LlamaForCausalLM.forward

    def half(self, input_ids, labels=None, **kw):
        n = input_ids.shape[0] // 2
        return whole(self, input_ids[:n],
                     labels=None if labels is None else labels[:n], **kw)

    monkeypatch.setattr(LlamaForCausalLM, "forward", half)
    line = _run(*_tiny_train())
    assert not line["correct"], line["checks"]


def test_a_loader_that_drops_a_batch_is_not_correct(monkeypatch):
    """The reference follows the rows the benchmark itself built, not
    what the program's loader delivered."""
    import paddle_tpu as paddle

    real = paddle.io.DataLoader

    class Dropping(real):
        def __iter__(self):
            it = super().__iter__()
            next(it)
            return it

    monkeypatch.setattr(paddle.io, "DataLoader", Dropping)
    line = _run(*_tiny_train())
    assert not line["correct"]
    assert line["checks"]["fed_rows_differ"]["value"] == 6    # 3 x 2 rows


def test_rows_that_differ_counts_rows():
    from benchmarks import train

    a = [np.arange(8).reshape(2, 4), np.arange(8).reshape(2, 4)]
    b = [a[0].copy(), a[1][::-1].copy()]
    assert train.rows_that_differ(a, a) == 0
    assert train.rows_that_differ(b, a) == 2
    assert train.rows_that_differ([a[0][:1], a[1]], a) == 2


def test_serving_control_reads_wider_than_the_program():
    from benchmarks import serve

    _, cfg, _ = _tiny_serve("open")
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, 256, n)] for n in (40, 70)]
    from benchmarks.reference import decoder

    logits = decoder.served_gaps(cfg, 4, [(p, [0] * 12) for p in prompts])
    # greedy answers of the reference itself, one step (teacher forcing
    # is exact for the first token; the rest only has to be some answer)
    seqs = [(p, [int(l[0].argmax())] + [int(t) for t in
                                         rng.integers(0, 256, 11)])
            for p, l in zip(prompts, logits)]
    own = serve.widest_gap(cfg, 4, [(p, o[:1]) for p, o in seqs])
    assert own == 0.0
    control = serve.widest_gap(cfg, 4, seqs, mode="fp8")
    assert control > cfg["limits"]["served_logit_gap_max"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.serving import Engine

    step = Engine.step
    altered = set()

    def meddling(self):
        out = step(self)
        for r in self.slots:
            if (r is not None and len(r.output_token_ids) >= 3
                    and id(r) not in altered):
                altered.add(id(r))
                r.output_token_ids[1] = (r.output_token_ids[1] + 97) % 256
        return out

    monkeypatch.setattr(Engine, "step", meddling)
    line = _run(*_tiny_serve("open"), seconds=2.0)
    assert altered and not line["correct"]
    gap = line["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]
