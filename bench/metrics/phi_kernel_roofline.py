"""The Phi passes' share of their roofline, in %, over the Phi work alone.

Least time: every Phi pass of the window's solves (one per inner
iteration, and the scooch's one per mode and sweep) at ``counts.phi_pass``
operations and the smallest per-mode bytes, at the peaks of
``peaks.json``; no Khatri-Rao products.  Over: the self time of the device
ops whose innermost scope is ``cpapr.phi`` (the Pallas kernel on the
Pallas path, the row products and ``segment_sum`` on the segment path),
without the layout and epilogue ops around them.
"""

import counts
import spans


def read(record):
    w = spans.of(record)
    phi_s = spans.scope_seconds(w).get("cpapr.phi", 0.0) if w else 0.0
    if phi_s <= 0 or not record.get("solves"):
        return None
    nnz, dims, rank = (record["work"][k] for k in ("nnz", "dims", "rank"))
    passes = sum(s["inner"] + s["n_outer"] * len(dims)
                 for s in record["solves"])
    flops, _ = counts.phi_pass(nnz, dims, rank, 0)
    least_bytes = min(counts.phi_pass(nnz, dims, rank, n)[1]
                      for n in range(len(dims)))
    least, bound = counts.least_time(passes * flops, passes * least_bytes,
                                     record["peaks"])
    print(f"phi_kernel_roofline.solve: least time {least!r} s ({bound}-"
          f"bound, {passes} passes) over {phi_s!r} s of cpapr.phi ops",
          flush=True)
    return 100.0 * least / phi_s
