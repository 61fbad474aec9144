"""Device values read on the host per outer sweep: the ``host_syncs``
counter over the ``sweeps`` counter, summed over the window's
``cpapr.solve`` spans, which carry both as attributes."""

import spans


def read(record):
    w = spans.of(record)
    stats = [st for *_, st in spans.named(w, "cpapr.solve")] if w else []
    stats = [st for st in stats if "host_syncs" in st and "sweeps" in st]
    sweeps = sum(st["sweeps"] for st in stats)
    if not sweeps:
        return None
    return sum(st["host_syncs"] for st in stats) / sweeps
