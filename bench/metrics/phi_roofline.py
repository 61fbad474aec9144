"""The mode updates' share of their roofline, in %.

Least time: the work of every solve in the window as ``counts.solve_work``
counts it (each Phi pass at its least bytes and operations), at the peaks
of ``peaks.json``, whichever of memory and compute bounds it (printed).
Over: the device time of the mode-update programs in the traced window,
the jitted ``_update`` of ``cpapr._make_mode_update`` (Pi build, scooch,
fused Phi -> MU inner loop and renormalisation in one program), which XLA
names ``jit__update``.  Where the trace shows none, there is no reading.
"""

import counts

MODULES = ("jit__update",)


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    device_s = sum(t for name, t in tr["modules"].items()
                   if name.split("(")[0] in MODULES)
    if device_s <= 0 or not record.get("solves"):
        return None
    w = record["work"]
    flops = nbytes = 0
    for s in record["solves"]:
        f, b = counts.solve_work(w["nnz"], w["dims"], w["rank"],
                                 s["n_outer"], s["inner"])
        flops, nbytes = flops + f, nbytes + b
    least, bound = counts.least_time(flops, nbytes, record["peaks"])
    print(f"phi_roofline.solve: least time {least!r} s ({bound}-bound: "
          f"{flops!r} flops, {nbytes!r} bytes) over {device_s!r} s of "
          f"mode updates", flush=True)
    return 100.0 * least / device_s
