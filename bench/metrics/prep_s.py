"""Mean seconds of the program's ``cpapr.prepare`` span over the window's
solves: all that ``cpapr_mu`` does before its first sweep (validation,
per-mode sorts, policy and layouts, building the mode updates), on the
trace's host clock.  Prints the mean per solve of each child span."""

import spans

CHILDREN = ("cpapr.validate", "cpapr.sort", "cpapr.policy", "cpapr.build")


def read(record):
    w = spans.of(record)
    prepares = spans.named(w, "cpapr.prepare") if w else []
    if not prepares:
        return None
    n = len(prepares)
    parts = {c: sum(e - s for _, s, e, _ in spans.named(w, c)) / n / 1e9
             for c in CHILDREN}
    print(f"prep_s.solve: {n} solves; seconds per solve {parts!r}",
          flush=True)
    return sum(e - s for _, s, e, _ in prepares) / n / 1e9
