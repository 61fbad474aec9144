"""Share of the window's device-idle time that lies inside the program's
``cpapr.prepare`` spans, in %: how much of what holds the chip back is the
solve's host work before its first sweep.  Prints the idle seconds by the
innermost ``cpapr.*`` span over them, with ``bench.solve`` for idle time
in a solve under none, and ``(outside)`` for the rest."""

import spans


def read(record):
    w = spans.of(record)
    prepares = spans.named(w, "cpapr.prepare") if w else []
    if not prepares:
        return None
    idle = spans.idle_within(w)
    total = idle(*w["window"])
    if total <= 0:
        return None
    by = sorted(spans.idle_by_span(w).items(), key=lambda kv: -kv[1])
    print(f"idle_prep_share.solve: {total / 1e9!r} s idle; by span {by!r}",
          flush=True)
    return 100.0 * sum(idle(s, e) for _, s, e, _ in prepares) / total
