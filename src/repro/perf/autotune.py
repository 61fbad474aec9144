"""Online, persistent parallel-policy autotuner for the Phi kernels.

The paper shows grid search over the parallel policy gives 2.25x (CPU) /
1.70x (GPU) over defaults but leaves selection as an offline exercise
("an obvious next step", Sec. 5).  This module makes it *online*:

  * :class:`Autotuner` keys each tuning problem on
    ``(platform, nnz, n_rows, rank)`` **plus the mode's binned
    segment-run statistics** (p95 run length, max-row duplication share,
    empty-row fraction — see :func:`repro.core.layout.mode_run_stats`).
    The SparTen parameter study (Myers et al., arXiv:2012.01520) shows
    the best policy depends on the nonzero *distribution*, so a
    hub-dominated mode and a uniform mode with identical size stats get
    distinct cache entries; the stats are bucketed into coarse bins so
    nearby tensors still share one.
  * on a cache miss it measures a *pruned* policy grid (the heuristic's
    neighborhood plus the unblocked strategies).  The default probe is a
    short jitted ``lax.while_loop`` **burst** of fused MU steps — the
    same loop shape ``cpapr_mu`` runs — so the measurement captures the
    revisit/cache effects a one-shot call misses (set ``burst=1`` for
    the legacy single-call probe);
  * **model-guided probe pruning** (``model_guided=True``, the default
    for measuring tuners): every candidate's burst program is
    AOT-compiled, costed with :func:`repro.perf.hlo_costs.module_costs`,
    and scored with the 3-term roofline
    (:func:`repro.perf.roofline.roofline_terms`) against a
    :class:`HardwareSpec` detected from the *actual* backend.  Only the
    model's top-K candidates (family winners guaranteed a slot — see
    :func:`repro.core.policy.model_top_k`) are measured, reusing the
    already-compiled executables, so pruning never pays a second
    compile.  Entries record ``model_s``/``measured_s``; once the
    store holds enough (model, measured) pairs to calibrate a trailing
    error bound, keys whose predicted margin between the top two
    candidates exceeds that bound are served **model-only with zero
    probes** (``source="model"``) — cold keys under production traffic
    then cost one compile pass, no timing loops at all;
  * when measurement is disabled or every probe fails it falls back to
    a migrated v1 winner (if one is quarantined for the same problem) or
    :func:`repro.core.policy.heuristic_policy`; probe failure reasons are
    recorded in the cache entry (``probe_errors``) instead of vanishing;
  * winners persist in a JSON store (:class:`AutotuneCache`) so repeat
    decompositions — including in *future processes* — pay zero search
    cost.

Cache schema v2.  The store is a plain JSON object::

    {"version": 2,
     "entries": {v2_key: {"policy": {...}, "seconds": float|null,
                          "source": "grid"|"heuristic"|"migrated-v1",
                          "schema": 2, "jax": "<jax.__version__>",
                          "device_kind": "<device_kind>", "probe": "...",
                          "burst": int, "stats": {...}, "tuned_at": ts,
                          "probe_errors": [...]}},
     "quarantined": {key: {"entry": <raw>, "reason": "..."}}}

written atomically (tmp file + rename) after every new winner.  Entries
carry staleness metadata (jax version, device kind, schema version): a
*measuring* tuner treats mismatching entries as misses and re-tunes; a
non-measuring tuner still serves them (a stale measured winner beats an
unmeasured heuristic).  Loading a v1 store (or a v2 store with corrupt
entries) never crashes: unusable entries are *quarantined* — preserved
under ``"quarantined"`` with a reason, never served directly.  Each v1
entry is migrated the first time its problem is tuned again (adopted as
the fallback policy under its new v2 key, ``source="migrated-v1"``).

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.

``CPAPRConfig(policy="auto")`` consults this per mode (see
``repro.core.cpapr``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layout import ModeStats, build_blocked_layout, mode_run_stats
from repro.core.phi import expand_to_layout, phi_mu_step
from repro.core.policy import (
    SEARCH_ERRORS,
    PhiPolicy,
    grid_search,
    heuristic_policy,
    model_ambiguous_prefix,
    model_top_k,
    vmem_footprint_bytes,
)

__all__ = [
    "AutotuneCache",
    "Autotuner",
    "current_device_kind",
    "default_cache_path",
    "policy_key",
    "shard_assignment_fragment",
]


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json")


def current_device_kind() -> str:
    """Device kind of the default backend (staleness metadata)."""
    try:
        return str(jax.devices()[0].device_kind)
    except Exception:  # pragma: no cover - backend init failure
        return "unknown"


def policy_key(
    nnz: int,
    n_rows: int,
    rank: int,
    platform: str,
    n_shards: int = 1,
    stats: ModeStats | None = None,
    assign: str | None = None,
    combine: str | None = None,
    grid: "tuple | None" = None,
) -> str:
    """Cache key for one tuning problem.

    With ``stats`` (a :class:`repro.core.layout.ModeStats`) the key is the
    v2 format: a ``v2/`` prefix plus the binned segment-run dimensions, so
    equal-size modes with different nonzero distributions resolve to
    distinct entries.  Without ``stats`` the legacy v1 format comes back —
    used for migration bookkeeping and by direct store users.

    ``n_shards`` > 1 appends a ``/shards=N`` dimension, so sharded-mode
    entries never collide with (or shadow) the single-device entries.
    ``assign`` (a :func:`shard_assignment_fragment`) further appends an
    ``/assign=...`` dimension: the same shard *count* under a different
    block->shard assignment (e.g. after nnz-weighted rebalancing) is a
    different tuning problem, so rebalanced assignments never shadow the
    static split's winners.  ``combine`` appends a ``/combine=...``
    dimension for the non-default sharded epilogue (reduce-scatter): its
    communication/revisit profile differs from the psum path, so winners
    tuned under one combine never silently serve the other (``"psum"``
    and ``None`` keep the PR-2..4 keyspace — old entries stay valid).
    ``grid`` (an ``(A, B)`` device-grid shape with ``B > 1``) appends a
    ``/grid=AxB`` dimension: a cell of an N-D grid revisits rows the 1D
    shard of the same size never splits, so grid winners and 1D winners
    stay separate entries (``B == 1`` *is* the 1D split and keeps the 1D
    keyspace).
    """
    base = f"{platform}/nnz={nnz}/rows={n_rows}/rank={rank}"
    if stats is not None:
        base = f"v2/{base}/{stats.key_fragment()}"
    if n_shards in (None, 1):
        return base
    key = f"{base}/shards={n_shards}"
    if assign is not None:
        key = f"{key}/assign={assign}"
    if combine not in (None, "psum"):
        key = f"{key}/combine={combine}"
    if grid is not None and int(grid[1]) > 1:
        key = f"{key}/grid={int(grid[0])}x{int(grid[1])}"
    return key


def shard_assignment_fragment(cuts) -> str:
    """Short stable signature of a shard assignment's stream cuts.

    Deterministic across processes (crc32 of the cut positions), so a
    rebalanced assignment re-keys the same way in every future run.
    """
    import zlib

    arr = np.asarray(list(cuts), np.int64)
    return format(zlib.crc32(arr.tobytes()) & 0xFFFFFFFF, "08x")


def _policy_to_json(p: PhiPolicy) -> dict:
    return dataclasses.asdict(p)


def _policy_from_json(d: dict) -> PhiPolicy:
    return PhiPolicy(**d)


def _stats_to_json(stats: ModeStats | None) -> dict | None:
    if stats is None:
        return None
    out = {
        "p95_run": stats.p95_run,
        "max_run": stats.max_run,
        "dup_share": round(stats.dup_share, 6),
        "empty_frac": round(stats.empty_frac, 6),
    }
    if getattr(stats, "fill_bin", -1) >= 0:
        # fill provenance rides along when the caller measured it (it is
        # already part of the key via /fill=bN; this is for humans)
        out["fill_frac"] = round(stats.fill_frac, 6)
        out["fill_bin"] = int(stats.fill_bin)
    return out


def _env_int(name: str) -> int | None:
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def _env_float(name: str) -> float | None:
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


class AutotuneCache:
    """Persistent JSON store of tuned policies (schema v2).

    ``entries`` maps :func:`policy_key` strings to tuned-policy records
    (see the module docstring for the full field list).  ``quarantined``
    holds entries that could not be served — v1-schema records awaiting
    migration and corrupt v2 records — keyed by their original key with
    the quarantine reason attached.  Corrupt or missing *files* load as
    empty; all writes are atomic (tmp + ``os.replace``) and crc-stamped
    (``crc32`` over the canonical body dump, verified at load), so
    concurrent processes at worst lose a race, never the file — and a
    store that somehow carries interleaved writer output is detected and
    dropped instead of served.

    Long-lived fleets accumulate entries without bound (every tensor
    shape x distribution bin x shard assignment is a key), so the store
    supports two optional caps:

      * ``max_entries`` — LRU bound: every lookup that *serves* a policy
        stamps the entry's ``served_at``; the cap is enforced at load
        time and after every store()/migration, evicting the
        least-recently-served entries (``served_at``, falling back to
        ``tuned_at``).  Recency from a read-only process lives in memory
        and is persisted opportunistically by whichever process next
        writes the store — a deliberate trade against rewriting the JSON
        file on every lookup.  Quarantined records are an audit trail,
        not cache — they neither count toward nor are touched by the cap.
      * ``max_age_days`` — TTL: entries whose ``tuned_at`` is older are
        dropped at load time (a winner tuned months ago predates driver/
        library churn even when the jax version string matches).

    Defaults come from ``$REPRO_AUTOTUNE_MAX_ENTRIES`` /
    ``$REPRO_AUTOTUNE_MAX_AGE_DAYS``; unset means unbounded (the PR-1..3
    behaviour).
    """

    VERSION = 2

    @staticmethod
    def _body_crc(body: dict) -> str:
        """crc32 over the canonical dump of the store body.  Computed on
        *parsed* values, so it is stable across the JSON round trip and a
        reader can verify whatever bytes it managed to read."""
        import zlib

        blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
        return format(zlib.crc32(blob.encode()) & 0xFFFFFFFF, "08x")

    def __init__(
        self,
        path: str | None = None,
        max_entries: int | None = None,
        max_age_days: float | None = None,
    ):
        self.path = path or default_cache_path()
        if max_entries is None:
            max_entries = _env_int("REPRO_AUTOTUNE_MAX_ENTRIES")
        if max_age_days is None:
            max_age_days = _env_float("REPRO_AUTOTUNE_MAX_AGE_DAYS")
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        if max_age_days is not None and max_age_days <= 0:
            raise ValueError(f"max_age_days must be > 0, got {max_age_days}")
        self.max_entries = max_entries
        self.max_age_days = max_age_days
        self.n_expired = 0  # TTL drops at the last load
        self.n_evicted = 0  # LRU drops over this instance's lifetime
        self.n_crc_failures = 0  # stores rejected by the crc stamp
        self.entries: dict = {}
        self.quarantined: dict = {}
        self.load()

    # -- persistence ------------------------------------------------------
    def load(self) -> None:
        self.entries, self.quarantined = {}, {}
        self.n_expired = 0
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(data, dict):
            return
        crc = data.get("crc32")
        if isinstance(crc, str):
            # crc-stamped store (this schema's writers): verify before
            # serving anything.  A mismatch means interleaved/partial
            # writer output — quarantine-don't-crash: load as empty, the
            # next atomic save rewrites a consistent file.
            body = {k: data[k] for k in ("entries", "quarantined")
                    if k in data}
            if self._body_crc(body) != crc:
                self.n_crc_failures += 1
                return
        version = data.get("version")
        raw_q = data.get("quarantined")
        if isinstance(raw_q, dict):
            self.quarantined = dict(raw_q)
        raw = data.get("entries")
        if not isinstance(raw, dict):
            return
        if version == 1:
            # v1 store: nothing is served directly, everything is kept for
            # the per-problem migration path (see Autotuner._tune_key).
            for key, entry in raw.items():
                self.quarantined[key] = {"entry": entry, "reason": "v1-schema"}
            return
        if version != self.VERSION:
            return
        cutoff = (
            time.time() - self.max_age_days * 86400.0
            if self.max_age_days is not None
            else None
        )
        for key, entry in raw.items():
            if isinstance(entry, dict) and isinstance(entry.get("policy"), dict):
                if cutoff is not None and (
                    not isinstance(entry.get("tuned_at"), (int, float))
                    or entry["tuned_at"] < cutoff
                ):
                    self.n_expired += 1  # TTL: silently aged out
                    continue
                self.entries[key] = entry
            else:
                self.quarantined[key] = {"entry": entry,
                                         "reason": "malformed-entry"}
        # a bounded instance enforces its cap immediately, so a store
        # written by unbounded processes cannot stay over it
        self._evict_lru()

    def _evict_lru(self) -> None:
        """Drop least-recently-served entries beyond ``max_entries``."""
        if self.max_entries is None:
            return

        def recency(item):
            key, e = item
            stamp = e.get("served_at") or e.get("tuned_at") or 0.0
            return (stamp, key)  # deterministic tie-break

        while len(self.entries) > self.max_entries:
            victim = min(self.entries.items(), key=recency)[0]
            del self.entries[victim]
            self.n_evicted += 1

    def save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        body: dict = {"entries": self.entries}
        if self.quarantined:
            body["quarantined"] = self.quarantined
        payload = {"version": self.VERSION, "crc32": self._body_crc(body),
                   **body}
        fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- staleness --------------------------------------------------------
    @staticmethod
    def entry_is_stale(entry: dict) -> bool:
        """True when the entry was tuned under a different schema, jax
        version, or device kind than the current process."""
        return (
            entry.get("schema") != AutotuneCache.VERSION
            or entry.get("jax") != jax.__version__
            or entry.get("device_kind") != current_device_kind()
        )

    # -- lookup / store ---------------------------------------------------
    def lookup(
        self, key: str, source: "str | tuple | None" = None,
        fresh: bool = False,
    ) -> PhiPolicy | None:
        """Cached policy for ``key``.

        With ``source`` set (one name or a tuple of acceptable names),
        only entries tuned that way (e.g. ``"grid"``, ``("grid",
        "model")``) count — used to re-tune heuristic placeholders once
        measurement becomes available.  With ``fresh=True``, entries
        whose staleness metadata (schema / jax version / device kind)
        mismatches the current process are skipped too — a measuring
        tuner re-tunes them, a non-measuring one still serves them.
        """
        e = self.entries.get(key)
        if e is None:
            return None
        if source is not None:
            accept = (source,) if isinstance(source, str) else tuple(source)
            if e.get("source") not in accept:
                return None
        if fresh and self.entry_is_stale(e):
            return None
        try:
            pol = _policy_from_json(e["policy"])
        except (KeyError, TypeError):
            return None
        e["served_at"] = time.time()  # LRU recency (persisted on next save)
        return pol

    def store(
        self,
        key: str,
        policy: PhiPolicy,
        seconds: float,
        source: str,
        stats: ModeStats | None = None,
        probe: str | None = None,
        burst: int | None = None,
        probe_errors: list | None = None,
        extra: dict | None = None,
    ) -> None:
        entry = {
            "policy": _policy_to_json(policy),
            # inf (heuristic fallback: nothing measured) is not valid JSON
            "seconds": seconds if np.isfinite(seconds) else None,
            "source": source,
            "tuned_at": time.time(),
            "schema": self.VERSION,
            "jax": jax.__version__,
            "device_kind": current_device_kind(),
        }
        if stats is not None:
            entry["stats"] = _stats_to_json(stats)
        if probe is not None:
            entry["probe"] = probe
            entry["burst"] = burst
        if probe_errors:
            entry["probe_errors"] = probe_errors
        if extra:
            # model-guided provenance (model_s / measured_s / probes /
            # margin...) — plain JSON scalars only
            entry.update(extra)
        self.entries[key] = entry
        self._evict_lru()
        self.save()

    # -- model calibration ------------------------------------------------
    def model_error_stats(self, device_kind: str | None = None) -> dict:
        """Trailing model-vs-measured error over this store's entries.

        Every *probed* model-guided entry records the winner's roofline
        estimate (``model_s``) next to its measured time (``measured_s``).
        The roofline is systematically off by a hardware-efficiency
        factor (XLA:CPU does not hit spec-sheet peaks), so the useful
        error is *calibrated*: with ``r = measured/model``, the median of
        ``r`` is the scale bias and ``|ln(r / median_r)|`` the residual
        dispersion — what actually limits the model's ability to rank.
        Returns ``{n, median_ratio, p50_log_err, p95_log_err,
        rel_err_p50, rel_err_p95}`` (the ``rel_err_*`` columns are the
        raw uncalibrated ``|model - measured| / measured`` percentiles,
        reported in BENCH_phi.json).  Only entries from the same device
        kind count; ``n == 0`` means no calibration data yet.
        """
        if device_kind is None:
            device_kind = current_device_kind()
        ratios = []
        for e in self.entries.values():
            if e.get("device_kind") != device_kind:
                continue
            m, s = e.get("model_s"), e.get("measured_s")
            if (
                isinstance(m, (int, float)) and isinstance(s, (int, float))
                and np.isfinite(m) and np.isfinite(s) and m > 0 and s > 0
            ):
                ratios.append(s / m)
        if not ratios:
            return {"n": 0, "median_ratio": None, "p50_log_err": None,
                    "p95_log_err": None, "rel_err_p50": None,
                    "rel_err_p95": None}
        r = np.asarray(ratios, np.float64)
        med = float(np.median(r))
        log_err = np.abs(np.log(r / med))
        rel = np.abs(r - 1.0)  # |measured - model| / model, uncalibrated
        return {
            "n": int(r.size),
            "median_ratio": med,
            "p50_log_err": float(np.percentile(log_err, 50)),
            "p95_log_err": float(np.percentile(log_err, 95)),
            "rel_err_p50": float(np.percentile(rel, 50)),
            "rel_err_p95": float(np.percentile(rel, 95)),
        }

    # -- v1 migration -----------------------------------------------------
    def quarantined_policy(self, key: str) -> PhiPolicy | None:
        """Policy of a quarantined entry (v1 or corrupt), if parseable."""
        q = self.quarantined.get(key)
        if not isinstance(q, dict):
            return None
        entry = q.get("entry")
        if not isinstance(entry, dict):
            return None
        try:
            return _policy_from_json(entry["policy"])
        except (KeyError, TypeError):
            return None

    def migrate_quarantined(self, old_key: str, new_key: str) -> PhiPolicy | None:
        """Adopt a quarantined v1 winner under its v2 key.

        The policy is re-stored under ``new_key`` with
        ``source="migrated-v1"`` and *no current staleness stamp is
        forged*: the migrated entry keeps its v1 provenance, so a fresh
        (measuring) lookup still treats it as stale and re-tunes, while a
        non-measuring tuner serves it instead of an unmeasured heuristic.
        Returns the migrated policy, or None when ``old_key`` has nothing
        usable (the quarantined record is left in place either way, as an
        audit trail).
        """
        pol = self.quarantined_policy(old_key)
        if pol is None:
            return None
        old = self.quarantined[old_key]["entry"]
        entry = {
            "policy": _policy_to_json(pol),
            "seconds": old.get("seconds") if isinstance(old, dict) else None,
            "source": "migrated-v1",
            "tuned_at": time.time(),
            "schema": 1,  # honest provenance: fresh lookups skip it
            "jax": old.get("jax") if isinstance(old, dict) else None,
            "device_kind": None,
            "migrated_from": old_key,
        }
        self.entries[new_key] = entry
        self._evict_lru()
        self.save()
        return pol


def candidate_policies(
    nnz: int,
    n_rows: int,
    rank: int,
    platform: str,
    vmem_budget: int = 8 * 2**20,
    include_pallas: bool | None = None,
    stats: ModeStats | None = None,
) -> list:
    """Pruned search grid: unblocked strategies + the heuristic's blocked
    neighborhood (block sizes at 0.5x/1x/2x), VMEM-feasible points only.

    ~8 candidates instead of the full Cartesian grid (paper Exps. 3-5) —
    small enough to amortize in one decomposition, rich enough to capture
    the grid optimum on the evaluation tensors (tracked as "regret" in
    ``benchmarks/bench_policy.py``).  ``stats`` re-centers the blocked
    neighborhood on the distribution-aware heuristic.
    """
    if include_pallas is None:
        include_pallas = platform == "tpu"
    cands = [PhiPolicy(strategy="segment"), PhiPolicy(strategy="scatter")]
    base = heuristic_policy(
        nnz, n_rows, rank, vmem_budget=vmem_budget, platform="tpu", stats=stats
    )
    seen = set()
    for bn_mul in (0.5, 1.0, 2.0):
        for br_mul in (0.5, 1.0, 2.0):
            bn = int(np.clip(base.block_nnz * bn_mul, 64, 2048))
            br = int(np.clip(base.block_rows * br_mul, 8, 1024))
            if (bn, br) in seen:
                continue
            seen.add((bn, br))
            p = PhiPolicy(strategy="blocked", block_nnz=bn, block_rows=br)
            if vmem_footprint_bytes(p, rank) <= vmem_budget:
                cands.append(p)
                if include_pallas:
                    cands.append(dataclasses.replace(p, strategy="pallas"))
    return cands


@functools.partial(jax.jit, static_argnames=("n_rows", "strategy", "layout"))
def _jit_mu_step(rows, vals, pi, b, vals_e, pi_e, n_rows, strategy, layout):
    return phi_mu_step(
        rows,
        vals,
        pi,
        b,
        n_rows=n_rows,
        strategy=strategy,
        layout=layout,
        vals_e=vals_e,
        pi_e=pi_e,
    )


@functools.partial(
    jax.jit, static_argnames=("n_rows", "strategy", "layout", "burst")
)
def _jit_mu_burst(rows, vals, pi, b, vals_e, pi_e, n_rows, strategy, layout,
                  burst):
    """``burst`` fused MU steps under one ``lax.while_loop`` dispatch.

    Mirrors the loop shape of ``cpapr_mu``'s inner solve — same carried
    state, same per-step fused ``phi_mu_step`` — with ``tol=-1`` so the
    update always applies and B keeps evolving across iterations (the
    revisit pattern a one-shot probe never exercises).  As in the
    solver, a Pallas candidate's kernel operands are built once, before
    the loop.
    """
    operands = None
    if strategy == "pallas":
        from repro.kernels.phi import ops as phi_ops

        operands = phi_ops.phi_operands(vals_e, pi_e, layout.local_rows,
                                        layout.grid_rb)

    def cond(state):
        i, _, viol = state
        return (i < burst) & (viol > -1.0)

    def body(state):
        i, bb, _ = state
        b_new, viol = phi_mu_step(
            rows,
            vals,
            pi,
            bb,
            n_rows=n_rows,
            tol=-1.0,
            strategy=strategy,
            layout=layout,
            vals_e=vals_e,
            pi_e=pi_e,
            operands=operands,
        )
        return (i + 1, b_new, viol)

    _, bf, viol = jax.lax.while_loop(
        cond, body, (jnp.int32(0), b, jnp.asarray(jnp.inf, b.dtype))
    )
    return bf, viol


class Autotuner:
    """Measure-once, cache-forever policy selection.

    Counters (for tests and regret reporting):
      * ``n_hits``     — lookups served from the cache.
      * ``n_searches`` — cache misses that triggered a tune (grid
        measurement, v1 migration, or heuristic fallback).
      * ``n_grid_searches`` — misses that actually ran timed probes.
      * ``n_migrated`` — misses resolved by adopting a quarantined v1
        winner under its v2 key.
      * ``n_probes`` — individual timed policy probes (the cost the
        model-guided pruning exists to cut).
      * ``n_model_served`` — misses answered by the roofline model alone
        (zero probes: the predicted top-2 margin beat the trailing
        calibrated error bound).

    Model-guided knobs (measuring tuners only):
      * ``model_guided`` — score candidates with the roofline model and
        measure only the top-``model_top_k`` (family winners always keep
        a slot).  Falls back to the full measured grid whenever model
        scoring fails outright.
      * ``model_min_samples`` — (model_s, measured_s) pairs the store
        must hold before model-only serving is allowed.
      * ``model_margin_factor`` — how many calibrated p95 log-errors the
        predicted top-2 margin must exceed to skip probing entirely.
    """

    #: never trust the model to separate candidates closer than 25% even
    #: when the trailing error says it could — timing jitter alone can
    #: produce a deceptively small trailing p95 on few samples.
    MODEL_MIN_LOG_ERR = float(np.log(1.25))

    def __init__(
        self,
        cache_path: str | None = None,
        measure: bool = True,
        iters: int = 2,
        warmup: int = 1,
        burst: int = 8,
        vmem_budget: int = 8 * 2**20,
        platform: str | None = None,
        include_pallas: bool | None = None,
        cache_max_entries: int | None = None,
        cache_max_age_days: float | None = None,
        model_guided: bool = True,
        model_top_k: int = 3,
        model_min_samples: int = 3,
        model_margin_factor: float = 1.25,
    ):
        self.cache = AutotuneCache(cache_path, max_entries=cache_max_entries,
                                   max_age_days=cache_max_age_days)
        self.measure = measure
        self.iters = iters
        self.warmup = warmup
        self.burst = int(burst)
        if self.burst < 1:
            raise ValueError(f"burst must be >= 1, got {burst}")
        self.vmem_budget = vmem_budget
        self.platform = platform
        self.include_pallas = include_pallas
        self.model_guided = model_guided
        self.model_top_k = int(model_top_k)
        if self.model_top_k < 1:
            raise ValueError(f"model_top_k must be >= 1, got {model_top_k}")
        self.model_min_samples = int(model_min_samples)
        self.model_margin_factor = float(model_margin_factor)
        self._hw = None  # detected HardwareSpec, resolved lazily once
        self.n_hits = 0
        self.n_searches = 0
        self.n_grid_searches = 0
        self.n_migrated = 0
        self.n_probes = 0
        self.n_model_served = 0

    def counters(self) -> dict:
        """Lookup/search/probe counters as a plain dict.

        The serving layer's metrics surface (and ``bench_serve``) report
        these to prove the cross-tenant store works: repeat shapes show
        up as ``hits`` with no ``probes``.
        """
        return {
            "hits": self.n_hits,
            "searches": self.n_searches,
            "grid_searches": self.n_grid_searches,
            "migrated": self.n_migrated,
            "probes": self.n_probes,
            "model_served": self.n_model_served,
        }

    def hardware_spec(self):
        """The roofline HardwareSpec for this tuner's backend (detected
        from the actual platform, not an assumed TPU; cached)."""
        if self._hw is None:
            from repro.perf.roofline import detect_hardware_spec

            self._hw = detect_hardware_spec(self.platform)
        return self._hw

    # -- measurement ------------------------------------------------------
    @staticmethod
    def _probe_args(pol: PhiPolicy, rows, vals, pi, n_rows: int):
        """(layout, vals_e, pi_e) for one probe — the hoisted per-mode
        prologue the solver runs once per mode update."""
        if pol.strategy in ("blocked", "pallas"):
            layout = build_blocked_layout(
                np.asarray(rows), n_rows, pol.block_nnz, pol.block_rows
            )
            vals_e, pi_e = expand_to_layout(layout, vals, pi)
            return layout, vals_e, pi_e
        return None, None, None

    def _model_score(self, pol: PhiPolicy, rows, vals, pi, b, n_rows: int):
        """Roofline estimate of one fused MU step under ``pol``.

        AOT-compiles the burst program (``jit.lower(...).compile()`` —
        deliberately *not* the jit call cache, so the executable can be
        handed to :meth:`_time_policy` and measured without a second
        compile), parses the optimized HLO with
        :func:`repro.perf.hlo_costs.module_costs`, and combines the
        3-term roofline against the detected :class:`HardwareSpec`.

        Returns ``(model_s, runner)`` where ``runner`` is a zero-arg
        callable executing one burst.  ``model_s`` is in *model seconds*:
        the burst ``while_loop``'s trip count is not visible in the
        optimized HLO (the body is costed once), and XLA:CPU does not
        reach spec-sheet peaks — both are uniform multiplicative biases
        that the store's median-ratio calibration absorbs
        (:meth:`AutotuneCache.model_error_stats`), so only the *ranking*
        has to be right here.
        """
        from repro.perf.hlo_costs import module_costs
        from repro.perf.roofline import roofline_terms

        layout, vals_e, pi_e = self._probe_args(pol, rows, vals, pi, n_rows)
        if self.burst > 1:
            lowered = _jit_mu_burst.lower(
                rows, vals, pi, b, vals_e, pi_e, n_rows=n_rows,
                strategy=pol.strategy, layout=layout, burst=self.burst,
            )
        else:
            lowered = _jit_mu_step.lower(
                rows, vals, pi, b, vals_e, pi_e, n_rows=n_rows,
                strategy=pol.strategy, layout=layout,
            )
        compiled = lowered.compile()
        mc = module_costs(compiled.as_text())
        hw = self.hardware_spec()
        terms = roofline_terms(mc.flops, mc.bytes, mc.wire_bytes, n_chips=1,
                               hw=hw)
        # 3-term roofline + the small-problem overheads the roofline is
        # blind to: per-dispatch cost for large-result instructions,
        # serial-loop iteration cost for small-result ones (XLA:CPU's
        # while-loop form of scatter/segment reductions), and serial
        # scatter updates (zero coefficients on TPU specs = pure
        # roofline).
        n_large = mc.exec_instructions - mc.exec_small_instructions
        model_s = (
            terms.bound_s
            + n_large * hw.op_overhead_s
            + mc.exec_small_instructions * hw.serial_instr_s
            + mc.scatter_elems * hw.scatter_elem_s
        )
        if not (np.isfinite(model_s) and model_s > 0):
            raise ValueError(
                f"empty cost model for {pol.label()}: flops={mc.flops} "
                f"bytes={mc.bytes}"
            )

        def runner():
            return compiled(rows, vals, pi, b, vals_e, pi_e)

        return model_s, runner

    def _time_policy(self, pol: PhiPolicy, rows, vals, pi, b, n_rows: int,
                     runner=None):
        """Median seconds of one fused MU step under ``pol``.

        The default probe runs ``self.burst`` steps in one jitted
        ``lax.while_loop`` (matching the solver's inner loop, so revisit
        and cache effects are measured) and reports per-step time;
        ``burst=1`` falls back to the legacy single-call probe.  Layout
        build + expansion stay outside the timed region — the solver
        hoists them out of the inner loop too (one per mode update).  The
        per-nonzero arrays are jit *arguments*, never closure constants:
        XLA embeds closed-over arrays as literals, which distorts CPU
        timings by an order of magnitude.

        ``runner`` (from :meth:`_model_score`) is an already-AOT-compiled
        burst executable for this exact policy: timing it skips the jit
        path so a model-pruned candidate is never compiled twice."""
        from repro.perf.timing import bench_burst_seconds, bench_seconds

        self.n_probes += 1
        if runner is not None:
            if self.burst > 1:
                return bench_burst_seconds(
                    runner, burst=self.burst, pass_burst=False,
                    warmup=self.warmup, iters=self.iters,
                )
            return bench_seconds(runner, warmup=self.warmup,
                                 iters=self.iters)
        layout, vals_e, pi_e = self._probe_args(pol, rows, vals, pi, n_rows)

        if self.burst > 1:
            return bench_burst_seconds(
                _jit_mu_burst,
                rows,
                vals,
                pi,
                b,
                vals_e,
                pi_e,
                n_rows=n_rows,
                strategy=pol.strategy,
                layout=layout,
                burst=self.burst,
                warmup=self.warmup,
                iters=self.iters,
            )
        return bench_seconds(
            _jit_mu_step,
            rows,
            vals,
            pi,
            b,
            vals_e,
            pi_e,
            n_rows=n_rows,
            strategy=pol.strategy,
            layout=layout,
            warmup=self.warmup,
            iters=self.iters,
        )

    def _model_rank(self, cands, rows, vals, pi, b, n_rows: int):
        """Score every candidate with the roofline model.

        Returns ``(scored, runners, errors)``: ``scored`` is
        ``[(policy, model_s)]`` fastest-predicted-first for the
        candidates that scored, ``runners`` maps ``policy.label()`` to
        the AOT-compiled burst executable, and ``errors`` records why the
        rest failed (same shape as probe errors, tagged ``model:``).  An
        empty ``scored`` means the model is unusable for this problem and
        the caller must fall back to the full measured grid.
        """
        scored, runners, errors = [], {}, []
        for p in cands:
            try:
                s, runner = self._model_score(p, rows, vals, pi, b, n_rows)
            except SEARCH_ERRORS as e:
                errors.append(f"{p.label()}: model: {type(e).__name__}: {e}")
                continue
            scored.append((p, s))
            runners[p.label()] = runner
        scored.sort(key=lambda x: x[1])
        return scored, runners, errors

    def _model_serve_or_prune(self, key, scored, stats, n_cands: int):
        """Decide what the model ranking buys for one cold key.

        Returns a :class:`PhiPolicy` when the key can be served
        model-only — the predicted margin between the top two candidates
        exceeds the store's trailing calibrated error bound
        (floored at :data:`MODEL_MIN_LOG_ERR`), so measuring could not
        responsibly overturn the prediction; the entry is stored with
        ``source="model"`` and zero probes.  Otherwise returns the
        *ambiguous prefix* of the model's top-K — the candidates the
        error bound cannot separate, which are the only ones worth
        timing.
        """
        top = model_top_k(scored, k=self.model_top_k)
        est = self.cache.model_error_stats()
        if est["n"] < self.model_min_samples or len(top) < 2:
            return top  # not calibrated yet (or nothing to separate)
        log_err = max(est["p95_log_err"], self.MODEL_MIN_LOG_ERR)
        bound = float(np.exp(self.model_margin_factor * log_err))
        prefix = model_ambiguous_prefix(top, bound, cap=self.model_top_k)
        if len(prefix) > 1:
            return prefix
        pol, model_s = prefix[0]
        self.n_model_served += 1
        self.cache.store(
            key, pol, float("inf"), "model", stats=stats,
            extra={
                "model_s": model_s,
                "probes": 0,
                "n_candidates": n_cands,
                "model_margin": top[1][1] / model_s,
                "model_error_bound": bound,
                "calibration_n": est["n"],
            },
        )
        return pol

    def _tune_key(self, key: str, rows, vals, pi, b, n_rows: int,
                  rank: int, platform: str, stats: ModeStats | None = None,
                  v1_key: str | None = None) -> PhiPolicy:
        """Cache-or-tune one problem under an explicit cache key.

        ``v1_key`` is the legacy (stats-less) key for the same problem;
        when the store holds a quarantined v1 entry under it, that winner
        is migrated instead of falling back to the unmeasured heuristic.
        """
        nnz = int(rows.shape[0])
        # A heuristic placeholder (stored when measurement was disabled or
        # every probe failed), a stale entry (other jax version / device
        # kind / schema), or a migrated-v1 policy does not satisfy a
        # measuring tuner — re-tune instead of pinning it forever.  A
        # model-served entry does: it was written by a measuring tuner
        # whose calibrated margin test passed.
        hit = (
            self.cache.lookup(key, source=("grid", "model"), fresh=True)
            if self.measure
            else self.cache.lookup(key)
        )
        if hit is not None:
            self.n_hits += 1
            return hit

        migrated = (
            self.cache.quarantined_policy(v1_key) if v1_key is not None
            else None
        )
        self.n_searches += 1
        best_p, best_s, source = None, float("inf"), "heuristic"
        # probe provenance is only recorded when probes actually run
        probe = ("burst" if self.burst > 1 else "single") if self.measure \
            else None
        probe_errors: list = []
        extra: dict = {}
        if self.measure:
            cands = candidate_policies(
                nnz,
                n_rows,
                rank,
                platform,
                vmem_budget=self.vmem_budget,
                include_pallas=self.include_pallas,
                stats=stats,
            )
            to_measure, runners, scored = cands, {}, None
            extra = {"probes": len(cands), "n_candidates": len(cands)}
            if self.model_guided:
                scored, runners, model_errors = self._model_rank(
                    cands, rows, vals, pi, b, n_rows
                )
                probe_errors += model_errors
                if scored:  # at least one candidate scored: prune
                    served = self._model_serve_or_prune(key, scored, stats,
                                                        len(cands))
                    if isinstance(served, PhiPolicy):
                        return served
                    to_measure = [p for p, _ in served]
                    extra = {
                        "probes": len(to_measure),
                        "n_candidates": len(cands),
                        "model_pruned": len(cands) - len(to_measure),
                    }
            self.n_grid_searches += 1
            ranked = grid_search(
                lambda p: self._time_policy(p, rows, vals, pi, b, n_rows,
                                            runner=runners.get(p.label())),
                to_measure,
            )
            probe_errors += [
                f"{p.label()}: {err}" for p, _, err in ranked if err is not None
            ]
            if ranked and np.isfinite(ranked[0][1]):
                best_p, best_s, _ = ranked[0]
                source = "grid"
                if scored:
                    model_by_label = {p.label(): s for p, s in scored}
                    ms = model_by_label.get(best_p.label())
                    if ms is not None:
                        extra["model_s"] = ms
                        extra["measured_s"] = best_s
        if best_p is None and migrated is not None:
            # v1 migration path: adopt the old winner (it keeps its v1
            # provenance, so a later measuring tuner still re-tunes it).
            self.n_migrated += 1
            pol = self.cache.migrate_quarantined(v1_key, key)
            if pol is not None:
                if probe_errors:  # keep why the grid failed alongside it
                    self.cache.entries[key]["probe_errors"] = probe_errors
                    self.cache.save()
                return pol
        if best_p is None:
            best_p = heuristic_policy(
                nnz, n_rows, rank, vmem_budget=self.vmem_budget,
                platform=platform, stats=stats,
            )
        self.cache.store(key, best_p, best_s, source, stats=stats,
                         probe=probe,
                         burst=self.burst if probe is not None else None,
                         probe_errors=probe_errors, extra=extra)
        return best_p

    # -- public API -------------------------------------------------------
    def mode_key(
        self,
        rows,
        n_rows: int,
        rank: int,
        n_shards: int = 1,
        stats: ModeStats | None = None,
    ) -> tuple:
        """(v2 cache key, ModeStats) for one mode's problem — what
        :meth:`policy_for_mode` will key on (benchmarks report this)."""
        platform = self.platform or jax.default_backend()
        if stats is None:
            stats = mode_run_stats(np.asarray(rows), n_rows)
        key = policy_key(int(np.asarray(rows).shape[0]), n_rows, rank,
                         platform, n_shards=n_shards, stats=stats)
        return key, stats

    def policy_for_mode(
        self,
        rows,
        vals,
        pi,
        b,
        n_rows: int,
        rank: int,
        stats: ModeStats | None = None,
    ) -> PhiPolicy:
        """Tuned policy for one mode's Phi problem (cached by problem key).

        ``stats`` (the mode's :class:`ModeStats`, usually computed once by
        the solver next to the layout build) folds the segment-run
        distribution into the cache key; when omitted it is computed here
        from ``rows``.
        """
        platform = self.platform or jax.default_backend()
        if stats is None:
            stats = mode_run_stats(np.asarray(rows), n_rows)
        nnz = int(rows.shape[0])
        key = policy_key(nnz, n_rows, rank, platform, stats=stats)
        v1_key = policy_key(nnz, n_rows, rank, platform)
        # Dense-tier short-circuit: when the fill cut fires, the dense
        # policy is served straight from the heuristic — the probe
        # harness holds sparse-stream operands only (no densified
        # tensor), so dense candidates cannot be timed here.  The entry
        # is cached under the fill-keyed v2 key so repeat shapes skip
        # even the heuristic arithmetic.
        if getattr(stats, "fill_bin", -1) >= 0:
            hp = heuristic_policy(
                nnz, n_rows, rank, vmem_budget=self.vmem_budget,
                platform=platform, stats=stats,
            )
            if hp.strategy == "dense":
                hit = self.cache.lookup(key)
                if hit is not None and hit.strategy == "dense":
                    self.n_hits += 1
                    return hit
                self.n_searches += 1
                self.cache.store(key, hp, float("inf"), "heuristic",
                                 stats=stats,
                                 extra={"probes": 0, "dense_cut": True})
                return hp
        return self._tune_key(key, rows, vals, pi, b, n_rows, rank, platform,
                              stats=stats, v1_key=v1_key)

    def policy_for_cutout(self, cutout) -> PhiPolicy:
        """Tuned policy for a :class:`repro.core.cpapr.ModeCutout`.

        The cutout carries exactly the arrays the solver's per-mode
        update consumes (sorted rows/vals, hoisted Pi, scaled factor,
        run stats), so tuning it is tuning the real mode problem —
        lowered and measured in isolation instead of inside a solve.
        """
        return self.policy_for_mode(
            cutout.rows, cutout.vals, cutout.pi, cutout.b,
            n_rows=cutout.n_rows, rank=cutout.rank, stats=cutout.stats,
        )

    def policy_for_sharded_mode(
        self,
        rows,
        vals,
        pi,
        b,
        n_rows: int,
        rank: int,
        n_shards: int,
        stats: ModeStats | None = None,
        cuts: "list | None" = None,
        assign: str | None = None,
        combine: str | None = None,
        grid: "tuple | None" = None,
    ) -> tuple:
        """Tuned policies for one mode split into ``n_shards`` row shards.

        Each shard's sub-problem (its contiguous slice of the sorted
        stream, rebased to its local row window) is tuned and cached under
        a shard-dimension key with the *shard's own* segment-run stats.
        Because one program must run on every mesh device, the per-shard
        winners are reconciled to a single uniform policy — the winner of
        the largest-nnz shard, which dominates the critical path.  Returns
        ``(uniform_policy, per_shard_policies)``; shards that own no
        nonzeros get ``None`` in the per-shard list.

        ``pi`` may be ``None`` for a *non-measuring* tuner (probes never
        run, so the Pi rows are never read) — callers re-keying a
        rebalanced assignment mid-solve use this to avoid materializing
        the (nnz, R) array the shard-local Pi path exists to avoid.

        ``cuts`` (optional) pins the shard assignment explicitly: a list
        of ``n_shards + 1`` sorted-stream cut positions, e.g. from
        ``repro.core.layout.shard_stream_cuts`` after a rebalance.  The
        per-shard keys then gain an ``/assign=...`` dimension (``assign``
        overrides the auto-derived :func:`shard_assignment_fragment`), so
        a rebalanced assignment tunes separately from the static split.
        Without ``cuts`` the default nnz-balanced split keeps the PR-2
        keyspace (no assign dimension — old entries stay valid).
        ``combine`` (``"reduce_scatter"``; ``"psum"``/None keep the old
        keyspace) appends the sharded-epilogue dimension to each
        per-shard key, so policies tuned under the two combine flavours
        never collide.  ``grid`` (an ``(A, B)`` shape, ``B > 1``)
        appends the ``/grid=AxB`` dimension for N-D grid modes — the
        row-shard sub-problems are tuned as usual (a grid cell runs the
        same local kernels on a slice of its row shard) but cached
        apart from the 1D winners.
        """
        platform = self.platform or jax.default_backend()
        if pi is None and self.measure:
            raise ValueError("a measuring tuner needs the Pi rows to probe; "
                             "pass pi or use Autotuner(measure=False)")
        rows_np = np.asarray(rows)
        nnz = int(rows_np.shape[0])
        if n_shards <= 1 or nnz == 0:
            pol = self.policy_for_mode(rows, vals, pi, b, n_rows=n_rows,
                                       rank=rank, stats=stats)
            return pol, [pol] * max(1, n_shards)

        if cuts is not None:
            cuts = [int(c) for c in cuts]
            if (
                len(cuts) != n_shards + 1
                or cuts[0] != 0
                or cuts[-1] != nnz
                or any(b_ < a_ for a_, b_ in zip(cuts, cuts[1:]))
            ):
                raise ValueError(
                    f"cuts must be non-decreasing from 0 to nnz={nnz} with "
                    f"{n_shards + 1} entries, got {cuts}"
                )
            if assign is None:
                assign = shard_assignment_fragment(cuts)
        else:
            # contiguous nnz-balanced cuts, snapped forward to row
            # boundaries (a row never spans shards)
            cuts = [0]
            for s in range(1, n_shards):
                p = s * nnz // n_shards
                while 0 < p < nnz and rows_np[p] == rows_np[p - 1]:
                    p += 1
                cuts.append(max(p, cuts[-1]))
            cuts.append(nnz)

        per_shard: list = []
        best, best_nnz = None, -1
        for s in range(n_shards):
            c0, c1 = cuts[s], cuts[s + 1]
            if c1 <= c0:
                per_shard.append(None)
                continue
            row_lo = int(rows_np[c0])
            row_hi = int(rows_np[c1 - 1]) + 1
            local_rows = rows_np[c0:c1] - row_lo
            shard_stats = mode_run_stats(local_rows, row_hi - row_lo)
            key = policy_key(c1 - c0, row_hi - row_lo, rank, platform,
                             n_shards=n_shards, stats=shard_stats,
                             assign=assign, combine=combine, grid=grid)
            v1_key = policy_key(c1 - c0, row_hi - row_lo, rank, platform,
                                n_shards=n_shards)
            pol = self._tune_key(
                key,
                jnp.asarray(local_rows),
                vals[c0:c1],
                pi[c0:c1] if pi is not None else None,
                b[row_lo:row_hi],
                row_hi - row_lo,
                rank,
                platform,
                stats=shard_stats,
                v1_key=v1_key,
            )
            per_shard.append(pol)
            if c1 - c0 > best_nnz:
                best, best_nnz = pol, c1 - c0
        if best is None:  # every shard empty (cannot happen when nnz > 0)
            best = heuristic_policy(
                nnz, n_rows, rank, vmem_budget=self.vmem_budget,
                platform=platform,
            )
        return best, per_shard
