"""The serving runner: serving.Engine driven in-process by one loop on one
thread (add_request, step, stamp the tokens that came out).

The traffic file's `kind` picks the driver: `open_loop_trace` admits every
request when it is due, whatever the engine is doing, and times it from
when it was due; `closed_loop_list` gives each client its next request when
the last one's answer has returned. A warm-up stretch of the same traffic
runs before the window, so that the slots are in steady state when it
opens; it is set-up. Once the window has closed and the engine is gone,
`correct` runs the plain reference over a seeded sample of the finished
requests and compares the widest gap by which a served token's logit lies
below the reference's best.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmarks import traffic as TR
from benchmarks import work
from benchmarks.run import span
from benchmarks.train import build_model

SPANS = ("engine.step", "engine.add_request", "loadgen.wait")
PROBES = ("prefill_compiles", "prefill_ext_compiles", "decode_compiles",
          "cow_compiles", "verify_compiles")


class Tracked:
    """One request as the harness sees it: when it was due, and when each
    of its tokens came out, on the harness's own clock."""

    def __init__(self, planned, due, req, admitted):
        self.planned, self.due, self.req = planned, due, req
        self.admitted = admitted
        self.stamps = []              # time of every output token
        self.cached = 0               # tokens computed for it so far
        self.done = None


def build_engine(cfg, seed):
    from paddle_tpu.serving import Engine, EngineConfig

    model = build_model(cfg, seed)
    model.eval()
    return Engine(model, EngineConfig(**cfg["engine"]))


def warm_programs(engine, e):
    """Launch once, before the traffic starts, every program it can
    reach: the engine compiles a program at its first launch (from JAX's
    persistent cache after a cell's first run). A one-shot prompt that
    fills each bucket up to the chunk size, a chunked prompt whose last
    chunk fills it, two decoded tokens each; no two share a first page."""
    from paddle_tpu.serving import SamplingParams

    chunk = e["prefill_chunk_tokens"]
    lengths = [n for b in e["prefill_buckets"] if b <= chunk
               for n in (b, chunk + b)]
    for k, n in enumerate(lengths, 1):
        engine.add_request([k] * n, SamplingParams(max_new_tokens=2))
    while engine.has_unfinished():
        engine.step()


class Driver:
    """The loop both kinds of traffic share: step the engine, then stamp
    and account for what came out."""

    def __init__(self, run, engine, cfg):
        self.run, self.engine, self.cfg = run, engine, cfg
        self.live = []                # Tracked, admitted and unfinished
        self.finished = []
        self.window = None            # (open, close) once it is open
        self.vocab = cfg["vocab_size"]

    def admit(self, planned, due):
        from paddle_tpu.serving import SamplingParams

        ids = TR.prompt_ids(self.run.seed, planned, self.vocab)
        with span("engine.add_request"):
            req = self.engine.add_request(
                ids, SamplingParams(max_new_tokens=planned.output_len))
        t = Tracked(planned, due, req, time.perf_counter())
        self.live.append(t)
        return t

    def in_window(self, t):
        return self.window is not None and (
            self.window[0] <= t < self.window[1])

    def step(self):
        from paddle_tpu.serving.request import RequestState

        running = {id(t): t.req.state is RequestState.RUNNING
                   for t in self.live}
        with span("engine.step"):
            t0 = time.perf_counter()
            self.engine.step()
            now = time.perf_counter()
        counted = self.in_window(now)
        if counted:
            self.run.add("engine_step_ms", (now - t0) * 1e3)
            # the counted steps' own span, first start to last end: what
            # a rate of their tokens is taken over
            first = self.run.counts.setdefault("counted_steps_start", t0)
            self.run.counts["counted_steps_s"] = now - first
        still = []
        for t in self.live:
            r = t.req
            new = len(r.output_token_ids) - len(t.stamps)
            # tokens computed: prompt chunks and decoded tokens alike
            # advance num_cached; a finished request's last decode did too
            cached = (len(r.prompt_token_ids) + len(r.output_token_ids) - 1
                      if r.finish_reason else r.num_cached)
            cached = max(cached, t.cached)
            # what the prefix cache served was not computed
            t.cached = max(t.cached, min(r.timeline.prefix_hit_tokens,
                                         cached))
            if counted:
                self.run.count("required_flops", work.span_flops(
                    self.cfg, t.cached, cached)
                    + new * work.head_flops(self.cfg))
                decoded = new if running[id(t)] else max(new - 1, 0)
                self.run.count("prefill_tokens",
                               cached - t.cached - decoded)
                self.run.count("generated_tokens", new)
                if decoded:
                    self.run.count("decode_live_tokens", cached)
                    self.run.count("decode_slot_steps", 1)
            t.cached = cached
            for _ in range(new):
                if t.stamps and counted:
                    self.run.add("itl_ms", (now - t.stamps[-1]) * 1e3)
                t.stamps.append(now)
            if r.finish_reason:
                t.done = now
                self.finished.append(t)
            else:
                still.append(t)
        self.live = still
        return now


def open_loop(run, driver, plan, t, origin):
    """Admit every request that is due, step, repeat; the window is
    [origin + warmup_s, + seconds]. Then step on, admitting nothing,
    until every request due in the window has its first token."""
    opens = origin + t["warmup_s"]
    closes = opens + run.seconds
    queue = list(plan)
    mine = []
    while True:
        now = time.perf_counter()
        if driver.window is None and now >= opens:
            opens = now = run.open_window()
            closes = opens + run.seconds
            driver.window = (opens, closes)
            run.counts["unfinished_at_open"] = len(driver.live)
            run.counts["waiting_at_open"] = len(driver.engine.waiting)
        if now >= closes:
            run.close_window()
            run.counts["unfinished_at_close"] = len(driver.live)
            run.counts["waiting_at_close"] = len(driver.engine.waiting)
            break
        while queue and origin + queue[0].due_s <= now:
            p = queue.pop(0)
            due = origin + p.due_s
            tr = driver.admit(p, due)
            if due >= opens:
                mine.append(tr)
                run.add("lateness_ms", (tr.admitted - due) * 1e3)
        if driver.engine.has_unfinished():
            driver.step()
        else:
            nxt = origin + queue[0].due_s if queue else closes
            with span("loadgen.wait"):
                time.sleep(max(0.0, min(nxt, closes) - time.perf_counter()))
    run.counts["window_s"] = run.seconds
    limit = time.perf_counter() + 60.0
    while any(not m.stamps and not m.req.finish_reason for m in mine) \
            and time.perf_counter() < limit:
        driver.step()
    for m in mine:
        run.attempted += 1
        if not m.stamps or m.req.finish_reason == "error":
            run.failed += 1
        else:
            run.add("ttft_ms", (m.stamps[0] - m.due) * 1e3)


def closed_loop(run, driver, lists, t, origin):
    """Each client sends its next request when its last one's answer has
    returned; clients start `stagger_s` apart during the warm-up."""
    opens = origin + t["warmup_s"]
    closes = opens + run.seconds
    nxt = [0] * len(lists)
    current = [None] * len(lists)
    total = sum(len(lst) for lst in lists)
    while True:
        now = time.perf_counter()
        if driver.window is None and now >= opens:
            opens = now = run.open_window()
            closes = opens + run.seconds
            driver.window = (opens, closes)
        if now >= closes:
            run.close_window()
            break
        for c, lst in enumerate(lists):
            if now < origin + c * t["stagger_s"]:
                continue
            if current[c] is None or current[c].done is not None:
                if current[c] is not None and driver.in_window(
                        current[c].done):
                    run.attempted += 1
                    if current[c].req.finish_reason == "error":
                        run.failed += 1
                # a list that runs out starts again with fresh token ids
                lap, j = divmod(nxt[c], len(lst))
                current[c] = driver.admit(dataclasses.replace(
                    lst[j], index=lst[j].index + lap * total), now)
                nxt[c] += 1
        if driver.engine.has_unfinished():
            driver.step()
        else:
            with span("loadgen.wait"):
                time.sleep(0.001)
    run.counts["window_s"] = run.seconds


def sample_finished(seed, finished, cap):
    """A sample of finished requests drawn from the seed, the longest
    always in it, within the reference's budget of tokens."""
    done = [t for t in finished if t.req.finish_reason != "error"
            and len(t.req.output_token_ids) > 1]
    if not done:
        return []

    def size(t):
        return len(t.req.prompt_token_ids) + len(t.req.output_token_ids)

    longest = max(done, key=size)
    rest = [t for t in done if t is not longest]
    np.random.default_rng([int(seed), 7]).shuffle(rest)
    picked, total = [longest], size(longest)
    served = len(longest.req.output_token_ids)
    for t in rest:
        if len(picked) >= cap["requests"] or served >= cap["served_tokens"]:
            break
        if total + size(t) > cap["tokens"]:
            continue
        picked.append(t)
        total += size(t)
        served += len(t.req.output_token_ids)
    return [(list(t.req.prompt_token_ids), list(t.req.output_token_ids))
            for t in picked]


def logit_gaps(logits, served):
    """By how much each served token's logit lies below the best."""
    import jax.numpy as jnp

    served = jnp.asarray(served, jnp.int32)
    took = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return np.asarray(logits.max(-1) - took)


def widest_gap(cfg, seed, sequences, mode="float32"):
    """The number `correct` compares: over the sample, the widest gap of
    a served token below the reference's best. With a lower `mode` the
    reference stands in the program's place (the control): the token that
    the lower precision puts first at each position is read instead."""
    from benchmarks.reference import decoder

    ref = decoder.served_gaps(cfg, seed, sequences)
    if mode == "float32":
        tokens = [o for _, o in sequences]
    else:
        low = decoder.served_gaps(cfg, seed, sequences, mode=mode)
        tokens = [np.asarray(l.argmax(-1)) for l in low]
    return max(float(logit_gaps(l, o).max()) for l, o in zip(ref, tokens))


def run(run):
    from paddle_tpu.compilecache import enable_persistent_cache

    cfg, t = run.config, run.traffic
    enable_persistent_cache()
    engine = build_engine(cfg, run.seed)
    warm_programs(engine, cfg["engine"])
    driver = Driver(run, engine, cfg)
    run.span_names = SPANS
    kind = t["kind"]
    plan = (TR.open_loop_trace(t) if kind == "open_loop_trace"
            else TR.closed_loop_lists(t))
    loop = {"open_loop_trace": open_loop,
            "closed_loop_list": closed_loop}[kind]
    before = {p: getattr(engine.metrics, p) for p in PROBES}
    loop(run, driver, plan, t, time.perf_counter())
    m = engine.metrics
    # compile probes count from the warm-up on: stricter than the window
    compiles = sum(getattr(m, p) - before[p] for p in PROBES)
    run.check("compiles_since_warmup", compiles, 0)
    run.check("failed_requests", run.failed, 0)
    wrong = sum(len(x.req.output_token_ids) != x.planned.output_len
                for x in driver.finished)
    run.check("wrong_length_answers", wrong, 0)
    run.counts["prefix_hit_tokens"] = m.prefix_hit_tokens
    run.counts["kilo_prefill_tokens"] = run.counts.get(
        "prefill_tokens", 0) / 1e3
    sequences = sample_finished(run.seed, driver.finished, cfg["sample"])
    run.notes.append(
        f"finished {len(driver.finished)} requests; sample of "
        f"{len(sequences)} with {sum(len(o) for _, o in sequences)} served "
        f"tokens, longest {max((len(p) + len(o) for p, o in sequences), default=0)}")
    run.kept.update(sequences=sequences)
    run.read_memory_peak()
    del driver, engine, m
    gc.collect()
    if sequences:
        run.check("served_logit_gap_max",
                  widest_gap(cfg, run.seed, sequences),
                  cfg["limits"]["served_logit_gap_max"])
    else:
        run.check("sampled_requests", 0, -1)     # nothing finished: fails
