"""Where set-up's seconds went, from the program's own spans
(paddle_tpu.observability.spans: every finished span is in a bounded ring
with its thread's id, start and end in nanoseconds of `time.time_ns()`).

    {"reducer": "span_self_time",
     "args": {"names": ["jit.trace", "jit.lower"],
              "where": {"kind": "train_step"}}}

Set-up ends where the window's first step starts: the start of the first
of the newest `counts["steps"]` `loader.next` spans (`train_step` where a
program has no loader), since nothing calls the program between the
window and this reading but the reference, whose spans end later and are
not read. It starts `counts["setup_s"]` before that. The spans of `READ`
that the main thread (the one `runtime.import` ran on) finished inside
that interval are nested by their intervals, and the value is the self
time of the spans asked for, in seconds: a span's duration less what its
children cover. So the names of `READ`, each read once, and the two
pseudo-names add up to `setup_s`:

    _before_    set-up's start to the start of `runtime.import`: the
                interpreter, the caller's own imports, the backend's start
    _unnamed_   `setup_s` less `_before_` less the union of the spans read

A span that is not in `READ` is not nested: its time stays with the span
around it, or with `_unnamed_`. `where` keeps the spans whose attributes
equal its values. A program without the ring's reader or without
`runtime.import` (an older commit), a ring that has wrapped (the oldest
span goes first, and `runtime.import` is the oldest), no step or no
`setup_s`: None, never a partial answer.
"""
from __future__ import annotations

from benchmarks.reduce import self_times

# name -> the `kind`s read (None: whatever its attributes)
READ = {
    "runtime.import": None,
    "optimizer.init_state": None,
    "train_step.build": None,
    "train_step": None, "train_step.prepare": None,
    "train_step.launch": None, "train_step.rebind": None,
    "loader.start": None, "loader.next": None, "loader.wait": None,
    "loader.unpack": None, "loader.h2d": None,
    "jit.trace": ("train_step", "unwatched"),
    "jit.lower": ("train_step", "unwatched"),
    "jit.compile": ("train_step", "unwatched"),
}
BEFORE, UNNAMED = "_before_", "_unnamed_"


def _is_read(sp):
    kinds = READ.get(sp.name, ())
    return kinds is None or sp.attrs.get("kind") in kinds


def time_line(readings):
    """(seconds before `runtime.import`, seconds no span covers, [(span,
    self seconds)] of the main thread's set-up), or None."""
    try:
        from paddle_tpu.observability import spans

        last, ring = spans.last, spans.finished_spans()
    except (ImportError, AttributeError):
        return None
    steps = int(readings.counts.get("steps") or 0)
    setup_s = readings.counts.get("setup_s")
    imported = [sp for sp in ring if sp.name == "runtime.import"]
    if not steps or setup_s is None or len(imported) != 1:
        return None
    first = last("loader.next", steps) or last("train_step", steps)
    if not first:
        return None
    # seconds since `runtime.import` opened: whole nanoseconds until here
    origin, end_ns = imported[0].start_ns, first[0].start_ns
    before = setup_s - (end_ns - origin) * 1e-9
    if before < 0:
        return None
    main = [sp for sp in ring
            if sp.tid == imported[0].tid and _is_read(sp)
            and sp.start_ns >= origin and sp.end_ns <= end_ns]
    own = self_times(((sp.start_ns - origin) * 1e-9,
                      (sp.end_ns - origin) * 1e-9, i)
                     for i, sp in enumerate(main))
    parts = [(sp, own[i]) for i, sp in enumerate(main)]
    return before, setup_s - before - sum(s for _, s in parts), parts


def read(readings, names, where=None, part="setup"):
    if part != "setup":
        raise ValueError(f"span_self_time: no part {part!r}, only 'setup'")
    line = time_line(readings)
    if line is None:
        return None
    before, unnamed, parts = line
    total = 0.0
    for name in names:
        if name == BEFORE:
            total += before
        elif name == UNNAMED:
            total += unnamed
        else:
            total += sum(
                seconds for sp, seconds in parts if sp.name == name
                and all(sp.attrs.get(k) == v
                        for k, v in (where or {}).items()))
    return total
