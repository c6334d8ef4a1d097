"""Per-multiply flight recorder: a bounded ring of the last N products.

Every `multiply()` commits one record — shapes, occupancies, the driver
decisions the dispatch actually made (and *why*: tuned row, prediction,
config force, emulated-dtype default), filtering/eps stats, per-phase
milliseconds, and the memory high-water — into a ring of the last
``DBCSR_TPU_FLIGHT_N`` (default 32) multiplies.  When a production run
dies or a checksum trips, the recorder answers "what was the engine
doing for the last N products" without re-running under a profiler:
`perf/driver.py` dumps it on checksum failure, `bench.py` on any
error, and `dump()`/`to_json()` serve it on demand.

The reference has no analog — its STATISTICS block is cumulative only;
this is the black-box component of the ROADMAP's production-scale
north star.

Reentrancy: TAS group loops run `multiply()` inside `tas_multiply`,
so records form a stack — each nested multiply gets its own record and
commits independently.

Module-level imports are stdlib-only; `core.timings`/`core.stats` are
reached lazily (this module is imported by the multiply hot path).
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time

_ring: collections.deque = collections.deque(
    maxlen=max(1, int(os.environ.get("DBCSR_TPU_FLIGHT_N", "32")))
)
_current: list = []  # stack of in-flight records (nested multiplies)
_seq = 0

# the timed() regions whose per-multiply deltas make up the per-phase
# breakdown (single-chip engine + dense path)
_PHASES = (
    "multiply_index", "multiply_c_assemble", "multiply_stacks",
    "multiply_filter", "multiply_dense", "dense_canvas_ab",
    "dense_dot", "dense_carve",
)


def ring_capacity() -> int:
    return _ring.maxlen


def begin(**fields) -> dict:
    """Open a record for the multiply that is starting; hot paths fill
    it via `note`/`note_driver` until `commit`."""
    global _seq
    _seq += 1
    rec = {
        "seq": _seq,
        "t_unix": time.time(),
        "drivers": {},
        **fields,
    }
    rec["_t0"] = time.perf_counter()
    rec["_phase0"] = _phase_snapshot()
    _current.append(rec)
    return rec


def note(key: str, value) -> None:
    """Set a field on the innermost open record (no-op outside one)."""
    if _current:
        _current[-1][key] = value


def note_driver(driver: str, why: str, mnk=None, entries: int = 0) -> None:
    """Accumulate one stack-driver decision onto the open record."""
    if not _current:
        return
    d = _current[-1]["drivers"].setdefault(
        driver, {"stacks": 0, "entries": 0, "why": why})
    d["stacks"] += 1
    d["entries"] += entries
    if mnk is not None:
        d.setdefault("mnk", []).append(list(mnk))


_MAX_EVENTS_PER_RECORD = 64


def note_event(event: str, **fields) -> None:
    """Append one structured event (fault injected, breaker transition,
    driver failover) to the innermost open record's bounded ``events``
    list — the resilience layer's black-box entries.  No-op outside a
    record.

    Overflow drops the OLDEST entry: in a black box the events nearest
    the crash are the diagnostic ones.  ``events_total`` preserves the
    true count, so a truncated list is detectable (``events_total >
    len(events)``)."""
    if not _current:
        return
    rec = _current[-1]
    events = rec.setdefault("events", [])
    rec["events_total"] = rec.get("events_total", 0) + 1
    if len(events) >= _MAX_EVENTS_PER_RECORD:
        del events[0]
    events.append(dict(fields, event=event))


def commit(error: str | None = None) -> dict | None:
    """Close the innermost record: stamp duration, per-phase ms and
    memory high-water, then append it to the ring."""
    if not _current:
        return None
    rec = _current.pop()
    rec["dur_ms"] = round((time.perf_counter() - rec.pop("_t0")) * 1e3, 3)
    rec["phases_ms"] = _phase_delta(rec.pop("_phase0"))
    if error is not None:
        rec["error"] = error
    try:
        from dbcsr_tpu.core import stats

        rec["memory"] = stats.memory_high_water()
    except Exception:
        pass
    _ring.append(rec)
    try:
        from dbcsr_tpu.obs import profiler

        profiler.observe(rec)
    except Exception:
        pass  # profile folding must never fail a multiply
    return rec


def _phase_snapshot() -> dict:
    from dbcsr_tpu.core import timings

    snap = {}
    for name in _PHASES:
        st = timings._stats.get(name)
        if st is not None:
            snap[name] = st.total
    return snap


def _phase_delta(snap: dict) -> dict:
    from dbcsr_tpu.core import timings

    out = {}
    for name in _PHASES:
        st = timings._stats.get(name)
        if st is None:
            continue
        dt = st.total - snap.get(name, 0.0)
        if dt > 0:
            out[name] = round(dt * 1e3, 3)
    return out


def records() -> list:
    """Ring contents, oldest first."""
    return list(_ring)


def clear() -> None:
    _ring.clear()
    _current.clear()


def to_json() -> str:
    return json.dumps(records(), default=str)


def dump(out=None, path: str | None = None) -> None:
    """Human-readable dump of the ring (newest last).  ``path`` (or
    $DBCSR_TPU_FLIGHT_DUMP) additionally writes the full JSON."""
    if out is None:
        out = lambda s: print(s, file=sys.stderr)  # noqa: E731
    path = path or os.environ.get("DBCSR_TPU_FLIGHT_DUMP")
    recs = records()
    out(f" FLIGHT RECORDER — last {len(recs)} multiplies "
        f"(capacity {_ring.maxlen})")
    for r in recs:
        mnk = r.get("mnk") or ("?", "?", "?")
        drv = ",".join(
            f"{d}x{v['stacks']}({v['why']})"
            for d, v in sorted(r.get("drivers", {}).items())
        ) or r.get("algorithm", "-")
        phases = " ".join(
            f"{k.replace('multiply_', '').replace('dense_', 'd:')}="
            f"{v:.1f}"
            for k, v in (r.get("phases_ms") or {}).items()
        )
        err = f"  ERROR={r['error']}" if r.get("error") else ""
        if r.get("events"):
            kinds = ",".join(sorted({e["event"] for e in r["events"]}))
            err += f"  events={len(r['events'])}({kinds})"
        out(f"  #{r['seq']} {r.get('name', '?')} "
            f"{mnk[0]}x{mnk[1]}x{mnk[2]} occ={r.get('occ_c', '-')} "
            f"alg={r.get('algorithm', '?')} drivers=[{drv}] "
            f"eps={r.get('filter_eps')} {r.get('dur_ms', 0):.1f} ms "
            f"[{phases}]{err}")
    if path:
        with open(path, "w") as f:
            f.write(to_json())
        out(f"  (full JSON written to {path})")
