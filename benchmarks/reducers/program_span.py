"""Durations and attributes of the program's own spans
(paddle_tpu.observability.spans: every finished span is in a bounded ring,
start and end in nanoseconds of the profiler's host clock).

    {"reducer": "program_span",
     "args": {"names": ["loader.unpack", "loader.h2d"], "stat": "mean",
              "per": "steps", "scale": 1000.0}}

`per` names a count of the run (`steps`): the newest that many spans of
each name are read, which are exactly the window's, since nothing calls
the program after the window; a ring that no longer holds that many gives
None, never a partial answer. Without `per`, every span of the name that
the ring holds. `stat` is `mean`, `max` or `sum` of the durations in
seconds times `scale`, or, with `attr`, of that attribute. Several names
add up. `where` keeps the spans whose attributes equal its values. A
program without the ring's reader (an older commit) reads None.
"""
from __future__ import annotations

_STATS = {"mean": lambda xs: sum(xs) / len(xs), "max": max, "sum": sum}


def read(readings, names, stat="mean", per=None, attr=None, where=None,
         scale=1.0):
    try:
        from paddle_tpu.observability import spans

        last, finished = spans.last, spans.finished_spans
    except (ImportError, AttributeError):
        return None
    total = 0.0
    for name in names:
        if per is not None:
            n = int(readings.counts.get(per) or 0)
            found = last(name, n) if n else None
        else:
            found = [sp for sp in finished() if sp.name == name]
        if where:
            found = [sp for sp in found or ()
                     if all(sp.attrs.get(k) == v for k, v in where.items())]
        if not found:
            return None
        values = [sp.attrs.get(attr) if attr else sp.duration_s
                  for sp in found]
        if any(v is None for v in values):
            return None
        total += scale * _STATS[stat](values)
    return total
