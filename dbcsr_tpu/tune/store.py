"""Versioned promotion store layered over `acc.params`.

The params table (`acc/params/parameters_<kind>.json`) stays the ONE
table dispatch reads — zero new hot-path cost.  This module owns the
write side for the online tuner:

* **Atomic promotion** — `promote()` writes the winning row into the
  params table (via `params.save_entry`, which bumps the table
  generation under the table lock) and appends one provenance record to
  the device-kind-keyed promotion LEDGER
  (``promotions_<kind>.json``, written atomically: temp + rename).
  Each record carries the measure env, the trial stats, the previous
  row it displaced, the live roofline fraction at promotion time, and
  a monotone per-ledger generation counter.  The params generation
  bump is what retires stale plans: `mm.multiply`'s plan cache (which
  also caches the fused superstack decisions) keys on
  `params.generation()`, so no cached plan ever serves superseded
  parameters (pinned by `tests/test_tune.py`).

* **Demotion on regression** — `check_regressions()` reads the
  telemetry history store (`obs.timeseries`): when a promoted row's
  driver shows a live roofline fraction below
  ``DBCSR_TPU_TUNE_DEMOTE_RATIO`` (default 0.5) of the fraction
  recorded at promotion, the row is demoted — removed from the params
  table, the displaced row restored, a ``demote`` ledger record
  appended — and the generation bumps again.  The timeseries store is
  the judge, closing the loop.

Stdlib + `acc.params` only at import; obs layers are reached lazily.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from dbcsr_tpu.acc import params as params_mod
from dbcsr_tpu.tune._env import env_float as _env_float

_lock = threading.Lock()


def generation() -> int:
    """The params-table generation plan caches key on (delegates to
    `acc.params.generation`)."""
    return params_mod.generation()


def ledger_path(kind: Optional[str] = None) -> str:
    kind = kind or params_mod.device_kind()
    return os.path.join(params_mod._params_dir(),
                        f"promotions_{kind}.json")


def load_ledger(kind: Optional[str] = None) -> List[Dict]:
    """All promotion/demotion records, oldest first (empty when the
    tuner never promoted on this device kind)."""
    try:
        with open(ledger_path(kind)) as fh:
            recs = json.load(fh)
        return recs if isinstance(recs, list) else []
    except (OSError, ValueError):
        return []


def _write_ledger(recs: List[Dict], kind: Optional[str]) -> None:
    """Atomic replace: a reader (or a crash) never sees a torn ledger."""
    path = ledger_path(kind)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(recs, fh, indent=1)
    os.replace(tmp, path)


def _key_of(row: Dict) -> list:
    return [row["m"], row["n"], row["k"], str(row["dtype"]),
            int(row.get("stack_size", 0))]


def _lookup_exact(m, n, k, dtype, stack_size, kind) -> Optional[Dict]:
    """The CURRENT params row at exactly this key (None when absent) —
    the incumbent a promotion displaces and a demotion restores."""
    import numpy as np

    table = params_mod._load(kind)
    return table.get(params_mod._key(m, n, k, np.dtype(dtype).name,
                                     stack_size))


def _live_roofline(driver: str) -> Optional[float]:
    """The driver's latest live roofline fraction from the telemetry
    store (None when the store is off or holds no such series)."""
    try:
        from dbcsr_tpu.obs import timeseries as ts

        rows = ts.query("dbcsr_tpu_roofline_fraction",
                        labels={"driver": driver}, agg="last")
        vals = [r["value"] for r in rows if r.get("value") is not None]
        return float(vals[-1]) if vals else None
    except Exception:
        return None


def _observe(kind_of_event: str, args: Dict, counter: str,
             **counter_labels) -> None:
    """One promotion/demotion emission: counter + correlated bus event
    + a forced next telemetry sample (the judge must see the new row's
    cells soon)."""
    try:
        from dbcsr_tpu.obs import events as _events
        from dbcsr_tpu.obs import metrics as _metrics

        _metrics.counter(
            counter,
            f"online-tuner {kind_of_event.split('_', 1)[1]}s by the "
            "promotion store (dbcsr_tpu.tune.store)",
        ).inc(**counter_labels)
        _events.publish(kind_of_event, args, flight=True)
        from dbcsr_tpu.obs import timeseries as _ts

        _ts.request_sample(kind_of_event)
    except Exception:
        pass  # observability must never fail a promotion


def promote(entry: Dict, trial: Optional[Dict] = None,
            stack_size: Optional[int] = None,
            kind: Optional[str] = None) -> Dict:
    """Atomically promote one trial winner into the live params table.

    ``entry`` is the winning candidate row (driver/grouping/precision/
    gflops + m, n, k, dtype, stack_size, env as `acc.tune` stamps
    them).  ``stack_size`` re-keys the promotion at the MINED cell's
    production stack size (the trial may have timed a budget-clamped
    smaller stack; the row must replace the incumbent serving the live
    traffic), with the trial's own size kept in provenance.  Returns
    the ledger record."""
    import numpy as np

    kind = kind or params_mod.device_kind()
    row = dict(entry)
    row["dtype"] = np.dtype(row["dtype"]).name
    trial_stack = int(row.get("stack_size", 0))
    if stack_size is not None and int(stack_size) != trial_stack:
        row["trial_stack_size"] = trial_stack
        row["stack_size"] = int(stack_size)
    row["tuned_by"] = "dbcsr_tpu.tune"
    with _lock:
        prev = _lookup_exact(row["m"], row["n"], row["k"], row["dtype"],
                             row.get("stack_size", 0), kind)
        recs = load_ledger(kind)
        gen = (max((r.get("generation", 0) for r in recs), default=0)
               + 1)
        row["promoted_gen"] = gen
        rec = {
            "action": "promote",
            "generation": gen,
            "key": _key_of(row),
            "entry": row,
            "prev_row": dict(prev) if prev else None,
            "measure_env": row.get("env"),
            "trial": dict(trial or {}),
            "roofline_at_promotion": _live_roofline(row.get("driver", "")),
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            # unix time: the regression judge only counts samples taken
            # AFTER this instant (points from the displaced row's
            # regime must not condemn the fresh promotion)
            "t_unix": time.time(),
        }
        recs.append(rec)
        _write_ledger(recs, kind)
        # save_entry bumps the params generation under the table lock:
        # every plan cached against the old generation is stale the
        # moment this returns
        params_mod.save_entry(row, kind=kind)
    _observe("tune_promotion",
             {"mnk": f"{row['m']}x{row['n']}x{row['k']}",
              "dtype": row["dtype"], "driver": row.get("driver"),
              "gflops": row.get("gflops"), "generation": gen,
              "displaced": (prev or {}).get("driver")},
             "dbcsr_tpu_tune_promotions_total",
             driver=str(row.get("driver")))
    return rec


def demote(m: int, n: int, k: int, dtype, stack_size: int,
           reason: str = "regression", kind: Optional[str] = None) -> bool:
    """Demote a promoted row: remove it from the params table, restore
    the row it displaced (when one existed), and append a ``demote``
    ledger record.  Both table writes bump the params generation, so
    plans built against the regressed row retire immediately.  Returns
    False when no live promotion exists at this key."""
    import numpy as np

    kind = kind or params_mod.device_kind()
    dtype = np.dtype(dtype).name
    key = [m, n, k, dtype, int(stack_size)]
    with _lock:
        recs = load_ledger(kind)
        live = _fold_live(recs).get(tuple(key))
        if live is None:
            return False
        params_mod.delete_entry(m, n, k, dtype, stack_size, kind=kind)
        prev = live.get("prev_row")
        if prev:
            params_mod.save_entry(dict(prev), kind=kind)
        else:
            # delete_entry only bumps on a real removal; a ledger whose
            # row was already hand-removed must still retire plans
            params_mod.invalidate()
        gen = max((r.get("generation", 0) for r in recs), default=0) + 1
        recs.append({
            "action": "demote",
            "generation": gen,
            "key": key,
            "reason": reason,
            "demoted_entry": live.get("entry"),
            "restored": bool(prev),
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        })
        _write_ledger(recs, kind)
    _observe("tune_demotion",
             {"mnk": f"{m}x{n}x{k}", "dtype": dtype, "reason": reason,
              "generation": gen,
              "driver": (live.get("entry") or {}).get("driver")},
             "dbcsr_tpu_tune_demotions_total", reason=reason)
    return True


def _fold_live(recs: List[Dict]) -> Dict[tuple, Dict]:
    """key-tuple -> latest promotion record still live (not superseded
    by a later demote of the same key)."""
    live: Dict[tuple, Dict] = {}
    for r in recs:
        key = tuple(r.get("key", ()))
        if r.get("action") == "promote":
            live[key] = r
        elif r.get("action") == "demote":
            live.pop(key, None)
    return live


def live_promotions(kind: Optional[str] = None) -> List[Dict]:
    """Promotion records currently in force (ledger folded)."""
    return sorted(_fold_live(load_ledger(kind)).values(),
                  key=lambda r: r.get("generation", 0))


# ------------------------------------------------- fleet-shared tier
#
# The serve product cache's peer tier (`serve.product_cache`) proved
# the envelope: same-fleet siblings answer bounded HTTP GETs, a dead
# peer costs ONE timeout then cools off, a structured miss never cools
# anything.  This applies the identical discipline to PROMOTIONS: a
# worker that tuned a cell serves its live ledger rows over
# ``GET /tune/promotions?kind=…`` (obs/server.py), and same-device-kind
# peers adopt them without re-trialing — the peer's trial evidence IS
# the evidence (same silicon, same crossover).

_peer_down: Dict[str, float] = {}


def _peers() -> List[str]:
    raw = os.environ.get("DBCSR_TPU_FLEET_PEERS", "")
    return [p.strip().rstrip("/") for p in raw.split(",") if p.strip()]


def _count_fleet(event: str) -> None:
    try:
        from dbcsr_tpu.obs import metrics as _metrics

        _metrics.counter(
            "dbcsr_tpu_tune_fleet_total",
            "fleet-shared tuning-promotion sync outcomes "
            "(dbcsr_tpu.tune.store.peer_sync)",
        ).inc(event=event)
    except Exception:
        pass


def export_promotions(kind: Optional[str] = None) -> Dict:
    """The wire form of this worker's live promotion rows for
    same-device-kind peers (the ``/tune/promotions`` route's payload).
    ORIGIN rows only: a row this worker itself adopted from a peer
    (``adopted_from``) never re-exports, so a promotion cannot echo
    around the fleet forever."""
    kind = kind or params_mod.device_kind()
    rows = []
    for rec in live_promotions(kind):
        entry = rec.get("entry") or {}
        if entry.get("adopted_from"):
            continue
        rows.append({"key": rec.get("key"), "entry": entry,
                     "generation": rec.get("generation"),
                     "t_unix": rec.get("t_unix")})
    return {"kind": kind, "rows": rows}


def peer_sync(kind: Optional[str] = None, peers=None) -> List[list]:
    """Adopt sibling workers' promoted params rows (fleet-shared
    tuning): for each reachable peer, fetch its live promotions and
    promote locally — through `promote`, so the adoption lands in the
    ledger, bumps the params generation (retiring cached plans), and
    stays demotable by the local regression judge.  A row is adopted
    only when the peer reports the SAME device kind (another chip's
    crossover does not transfer) and local evidence is not already at
    least as good.  Bounded: one ``DBCSR_TPU_FLEET_CACHE_TIMEOUT_S``
    timeout per peer, errors cool the peer off for
    ``DBCSR_TPU_FLEET_PEER_COOLOFF_S`` (a 404/miss never cools).
    Returns the adopted keys."""
    import json as _json
    import urllib.error as _uerr
    import urllib.request as _rq

    kind = kind or params_mod.device_kind()
    peers = _peers() if peers is None else peers
    if not peers:
        return []
    timeout = _env_float("DBCSR_TPU_FLEET_CACHE_TIMEOUT_S", 0.3)
    cooloff = _env_float("DBCSR_TPU_FLEET_PEER_COOLOFF_S", 30.0)
    adopted: List[list] = []
    now = time.monotonic()
    for peer in peers:
        with _lock:
            if _peer_down.get(peer, 0.0) > now:
                continue
        try:
            with _rq.urlopen(f"{peer}/tune/promotions?kind={kind}",
                             timeout=timeout) as resp:
                payload = _json.loads(resp.read().decode())
        except _uerr.HTTPError as exc:
            if exc.code == 404:
                # a healthy peer without the route/ledger is a miss,
                # never a cool-off (the serve cache tier's lesson)
                _count_fleet("peer_miss")
                continue
            with _lock:
                _peer_down[peer] = time.monotonic() + cooloff
            _count_fleet("peer_error")
            continue
        except Exception:
            with _lock:
                _peer_down[peer] = time.monotonic() + cooloff
            _count_fleet("peer_error")
            continue
        if str(payload.get("kind")) != kind:
            _count_fleet("kind_mismatch")
            continue
        for rec in payload.get("rows") or []:
            entry = rec.get("entry") or {}
            if not entry or entry.get("adopted_from"):
                continue
            try:
                m = int(entry["m"])
                n = int(entry["n"])
                k = int(entry["k"])
                dtype = str(entry["dtype"])
                s = int(entry.get("stack_size", 0))
            except (KeyError, TypeError, ValueError):
                continue
            incumbent = _lookup_exact(m, n, k, dtype, s, kind)
            if incumbent and incumbent.get("tuned_by") and \
                    float(incumbent.get("gflops") or 0.0) >= \
                    float(entry.get("gflops") or 0.0):
                continue  # local evidence already as good: no churn
            promote(dict(entry, adopted_from=peer),
                    trial={"adopted_from": peer,
                           "peer_generation": rec.get("generation")},
                    kind=kind)
            adopted.append([m, n, k, dtype, s])
            _count_fleet("adopted")
    return adopted


def check_regressions(kind: Optional[str] = None,
                      ratio: Optional[float] = None,
                      min_samples: int = 4,
                      query=None) -> List[Dict]:
    """The demotion judge: for every live promotion whose record
    carries an at-promotion roofline fraction, read the driver's
    recent live fraction from the telemetry store and demote the row
    when the recent median fell below ``ratio`` (default
    ``DBCSR_TPU_TUNE_DEMOTE_RATIO`` = 0.5) of the at-promotion value.
    ``query`` is injectable (tests); needs at least ``min_samples``
    post-promotion points before judging.  Returns the demoted ledger
    keys."""
    if ratio is None:
        ratio = _env_float("DBCSR_TPU_TUNE_DEMOTE_RATIO", 0.5)
    if query is None:
        try:
            from dbcsr_tpu.obs import timeseries as ts

            query = ts.query
        except Exception:
            return []
    from dbcsr_tpu.obs.windows import median

    demoted = []
    for rec in live_promotions(kind):
        frac0 = rec.get("roofline_at_promotion")
        entry = rec.get("entry") or {}
        driver = entry.get("driver")
        if not frac0 or not driver:
            continue
        try:
            rows = query("dbcsr_tpu_roofline_fraction",
                         labels={"driver": driver})
        except Exception:
            continue
        # POST-promotion samples only: trailing points from the
        # displaced row's regime would condemn a promotion that never
        # served a single request
        t0 = float(rec.get("t_unix", 0.0))
        pts = [v for r in rows for t, v in r.get("points", [])
               if t >= t0]
        pts = pts[-max(min_samples, 1):]
        if len(pts) < min_samples:
            continue
        recent = median(pts)
        if recent < ratio * float(frac0):
            m, n, k, dtype, s = rec["key"]
            if demote(m, n, k, dtype, s,
                      reason=f"regression:{recent:.4f}<"
                             f"{ratio:.2f}*{float(frac0):.4f}",
                      kind=kind):
                demoted.append(rec["key"])
    return demoted
