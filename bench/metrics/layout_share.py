"""Share of the mode updates' device time spent on layout, in %: the self
time of the device ops whose innermost scope is ``cpapr.layout`` (the
expansion into the blocked layout, lane pads, ``(N, 1)`` reshapes and the
slices back), over the device time of the mode-update programs
(``jit__update``).  Prints each scope's share of that time; what no scope
names is the rest.  Where no op carries a ``cpapr.*`` scope, there is no
reading."""

import spans

MODULE = "jit__update"


def read(record):
    w = spans.of(record)
    update_s = spans.module_seconds(w, MODULE) if w else 0.0
    by = spans.scope_seconds(w) if w else {}
    scoped = {k: v for k, v in by.items() if k is not None}
    if update_s <= 0 or not scoped:
        return None
    shares = {k: 100.0 * v / update_s for k, v in sorted(scoped.items())}
    print(f"layout_share.solve: {update_s!r} s of {MODULE}; scoped ops "
          f"{sum(shares.values())!r}% of it, by scope {shares!r}", flush=True)
    return shares.get("cpapr.layout", 0.0)
