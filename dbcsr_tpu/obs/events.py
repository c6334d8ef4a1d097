"""Unified structured-event bus with per-multiply correlation ids.

PRs 1–3 left the engine emitting rich but *disconnected* signals:
trace instants, flight-recorder event lists, breaker transitions,
watchdog verdicts, fault-injection instants — each site calling two or
three obs layers by hand, with nothing tying "this fallback, this
recompile, this roofline collapse" to *one multiply*.  This module is
the single choke point those sites now publish through:

* **Correlation** — `mm.multiply` opens a ``product_id`` per multiply
  (`begin_product`/`end_product`; nested TAS multiplies form a stack),
  and every event published while it is open is stamped with it.  The
  id also lands on the flight record and the multiply span, so all
  three stores join on one key (Dapper-style, scoped to a process).
* **Ring** — a bounded deque of the last ``DBCSR_TPU_EVENTS_N``
  (default 4096) events backs live reads: `obs.server`'s
  ``/events?product_id=…`` endpoint and `tools/doctor.py`.
* **JSONL sink** — opt-in streaming to disk, sharded per process like
  ``DBCSR_TPU_TRACE`` (``DBCSR_TPU_EVENTS=<base path>`` →
  ``<base>.p{process_index}<ext>``; a provisional hostname+pid name
  until `parallel.multihost.init_multihost` resolves the index).
* **Fan-out** — `publish` still forwards to the tracer instant and the
  flight-recorder event the call sites used to emit directly, so the
  existing trace/flight schemas are unchanged; the bus is additive.

Off switch: ``DBCSR_TPU_EVENTS=0`` disables the ring, the sink AND the
health-window sampling; `publish` then only forwards to trace/flight
exactly as the call sites did before this module existed — the
measured bus-off cost is one function call + two attribute checks per
event site.

Stdlib-only: `core.stats`/`acc.smm` reach this module from their hot
paths via `obs.metrics`/`obs.flight`, which must not pull in jax.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import uuid

from dbcsr_tpu.obs import flight as _flight
from dbcsr_tpu.obs import shard as _shard
from dbcsr_tpu.obs import tracer as _trace

_lock = threading.Lock()


def _env_capacity() -> int:
    raw = os.environ.get("DBCSR_TPU_EVENTS_N", "4096")
    try:
        return int(raw)
    except ValueError:
        return 4096


# "0"/"off" disables the bus entirely; a path enables the JSONL sink;
# unset/other keeps the default ring-only mode
_env = os.environ.get("DBCSR_TPU_EVENTS", "")
_enabled = _env not in ("0", "off")
_ring: collections.deque = collections.deque(
    maxlen=max(1, _env_capacity()))
_seq = 0

# product-id correlation stack (nested TAS multiplies), kept PER
# THREAD: the serving plane publishes submission/shed events from
# client threads while the worker thread has a multiply open — a
# global stack would stamp those events with the worker's product id
# (same rationale as core.mempool's thread-local chain stack)
_product_tls = threading.local()
_product_seq = 0


def _pstack() -> list:
    st = getattr(_product_tls, "stack", None)
    if st is None:
        st = _product_tls.stack = []
    return st


# process-unique token so ids from N multihost shards never collide
_TOKEN = f"{os.getpid():x}-{uuid.uuid4().hex[:6]}"

# JSONL sink state (sharded like the tracer; see module docstring)
_sink = None          # open file handle, or None
_sink_base: str | None = None
_sink_path: str | None = None
_sink_pid_final = False

# in-process subscribers (obs.rca's change ledger): called with the
# bus record AFTER it is ringed, outside _lock, each guarded — a
# subscriber can publish further events without deadlocking the bus
_subscribers: list = []


def subscribe(fn) -> None:
    """Register ``fn(record)`` to observe every bus record (after the
    ring append, outside the bus lock).  Idempotent per function."""
    if fn not in _subscribers:
        _subscribers.append(fn)


def unsubscribe(fn) -> None:
    try:
        _subscribers.remove(fn)
    except ValueError:
        pass


def enabled() -> bool:
    """True when the bus records (ring + sink + health sampling); when
    False `publish` only forwards to trace/flight."""
    return _enabled


def set_enabled(on: bool) -> None:
    """Tests / embedding apps: flip the bus without the env var."""
    global _enabled
    _enabled = bool(on)


def sink_active() -> bool:
    return _sink is not None


def sink_path() -> str | None:
    """The shard file the sink is currently writing (None when off)."""
    return _sink_path


# ------------------------------------------------------------ products

def begin_product(**fields) -> str:
    """Open a correlation id for the multiply that is starting; every
    event published until the matching `end_product` carries it."""
    global _product_seq
    with _lock:
        _product_seq += 1
        seq = _product_seq
    pid = f"{_TOKEN}-{seq}"
    _pstack().append(pid)
    publish("multiply_begin", dict(fields, product_id=pid))
    return pid


def current_product() -> str | None:
    """The innermost open product id on THIS thread (None outside a
    multiply)."""
    st = _pstack()
    return st[-1] if st else None


def end_product(rec: dict | None = None, error: str | None = None,
                **fields) -> None:
    """Close the innermost product: publish ``multiply_end`` carrying
    the flight record's summary (duration, driver decisions, flops) and
    feed the health model's rolling windows.  The product stays on the
    correlation stack until the health detectors ran, so an anomaly
    THIS multiply trips is stamped with its product_id."""
    st = _pstack()
    if not st:
        return
    pid = st[-1]
    args = dict(fields, product_id=pid)
    dur_ms = None
    if rec is not None:
        dur_ms = rec.get("dur_ms")
        args["dur_ms"] = dur_ms
        if rec.get("flops") is not None:
            args["flops"] = rec["flops"]
        if rec.get("algorithm"):
            args["algorithm"] = rec["algorithm"]
        if rec.get("drivers"):
            args["drivers"] = {
                d: v.get("stacks", 0) for d, v in rec["drivers"].items()}
    if error is not None:
        args["error"] = error[:300]
    publish("multiply_end", args)
    try:
        if _enabled:
            from dbcsr_tpu.obs import health as _health

            _health.observe_multiply(dur_ms=dur_ms, error=error)
    except Exception:
        pass  # health sampling must never fail a multiply
    finally:
        if st and st[-1] == pid:
            st.pop()
    # product boundary = a telemetry-store sample boundary (cadence-
    # gated inside; one attribute check when DBCSR_TPU_TS=0).  AFTER
    # the product popped: a forced sample's health collector must not
    # observe this multiply as still open.
    try:
        from dbcsr_tpu.obs import timeseries as _ts

        _ts.on_product()
    except Exception:
        pass  # telemetry must never fail a multiply


import contextlib as _contextlib


@_contextlib.contextmanager
def product_scope(op: str, name: str, **flight_fields):
    """One correlation scope around a multiply-like operation: opens a
    product id + flight record, commits/closes them on exit, and on
    error stamps both with the formatted exception before re-raising.
    Used by the distributed engines (`parallel/sparse_dist.py`);
    `mm.multiply` keeps its bespoke scope (it notes flops/algorithm on
    the record between body and commit)."""
    pid = begin_product(op=op, name=name)
    _flight.begin(op=op, product_id=pid, **flight_fields)
    try:
        yield pid
    except Exception as exc:
        err = f"{type(exc).__name__}: {exc}"[:300]
        rec = _flight.commit(error=err)
        end_product(rec=rec, error=err)
        raise
    rec = _flight.commit()
    end_product(rec=rec)


# ------------------------------------------------------------- publish

def publish(kind: str, args: dict | None = None, *, instant: bool = True,
            flight=False) -> dict | None:
    """Publish one structured event.

    ``args`` is the event payload; a ``product_id`` is stamped from the
    open correlation stack unless the payload already carries one.
    ``instant=True`` forwards a tracer instant of the same name (the
    pre-bus behavior of every call site); ``flight`` forwards a
    flight-recorder event — ``True`` reuses (kind, args), a
    ``(name, fields)`` tuple keeps a site's historical flight schema.

    Returns the bus record (None when the bus is disabled — the
    trace/flight fan-out still ran)."""
    global _seq
    args = args or {}
    pid = args.get("product_id")
    if pid is None:
        pid = current_product()
        if pid is not None:
            args = dict(args, product_id=pid)
    if instant:
        _trace.instant(kind, args or None)
    if flight:
        if flight is True:
            fname, ffields = kind, {
                k: v for k, v in args.items() if k != "product_id"}
        else:
            fname, ffields = flight
        _flight.note_event(fname, **ffields)
    if not _enabled:
        return None
    with _lock:
        _seq += 1
        # the envelope field is "event" (the flight recorder's
        # convention), NOT "kind": payloads legitimately carry their
        # own "kind" (fault kind, failure classification) and must not
        # be able to shadow the event name
        rec = {"seq": _seq, "t": time.time(), "event": kind, **args}
        rec["event"] = kind
        if "product_id" not in rec:
            rec["product_id"] = None
        _ring.append(rec)
        if _sink is not None:
            try:
                _sink.write(json.dumps(rec, default=str) + "\n")
            except Exception:
                pass  # a full disk must not fail the multiply
    for fn in list(_subscribers):
        try:
            fn(rec)
        except Exception:
            pass  # a broken subscriber must not fail the publisher
    return rec


# --------------------------------------------------------------- reads

def records(product_id: str | None = None, kind: str | None = None,
            limit: int | None = None) -> list:
    """Ring contents (oldest first), optionally filtered.  ``kind``
    filters on the envelope ``event`` name."""
    with _lock:
        out = list(_ring)
    if product_id is not None:
        out = [r for r in out if r.get("product_id") == product_id]
    if kind is not None:
        out = [r for r in out if r.get("event") == kind]
    if limit is not None and limit >= 0:
        out = out[-limit:]
    return out


def to_json(**filters) -> str:
    return json.dumps(records(**filters), default=str)


def clear() -> None:
    """Drop the ring (NOT the product stack: a clear mid-multiply must
    not orphan the open correlation id)."""
    with _lock:
        _ring.clear()


# ---------------------------------------------------------------- sink

def enable_sink(base_path: str | None = None) -> str:
    """Open the JSONL sink (default base: $DBCSR_TPU_EVENTS).  The base
    is sharded per process exactly like ``DBCSR_TPU_TRACE`` — see
    `tracer.shard_path`; the actual file is returned (and `sink_path`).
    Implies `set_enabled(True)`."""
    global _sink, _sink_base, _sink_path, _sink_pid_final
    base_path = base_path or os.environ.get("DBCSR_TPU_EVENTS")
    if not base_path or base_path in ("0", "off"):
        raise ValueError("no events sink path: pass one or set "
                         "DBCSR_TPU_EVENTS")
    disable_sink()
    set_enabled(True)
    pid = _shard.process_index()
    with _lock:
        _sink_base = base_path
        _sink_pid_final = pid is not None
        tag = pid if pid is not None else _shard.provisional_tag()
        _sink_path = _shard.shard_path(base_path, tag)
        _sink = open(_sink_path, "a")
    return _sink_path


def disable_sink() -> None:
    """Close the sink, settling a provisional shard name on index 0."""
    global _sink
    rebind(force=True)
    with _lock:
        if _sink is not None:
            try:
                _sink.close()
            except Exception:
                pass
            _sink = None


def rebind(process_index: int | None = None, force: bool = False) -> None:
    """Settle a provisionally-named sink shard onto its final
    ``p{index}`` name (same contract as `tracer.rebind`: called by
    `init_multihost` once the world's process index is known; ``force``
    settles on 0 at close).  Appends onto an existing final shard
    instead of clobbering it."""
    global _sink, _sink_path, _sink_pid_final
    with _lock:
        if _sink is None or _sink_pid_final:
            return
        if process_index is None:
            process_index = _shard.process_index()
        if process_index is None:
            if not force:
                return
            process_index = 0
        _sink_pid_final = True
        _sink_path, _sink = _shard.settle(
            _sink_base, _sink_path, _sink, int(process_index))


import atexit


@atexit.register
def _atexit_close() -> None:  # pragma: no cover - process teardown
    try:
        disable_sink()
    except Exception:
        pass


# env activation: DBCSR_TPU_EVENTS=<path> at import streams the bus to
# disk with no code changes anywhere (mirrors DBCSR_TPU_TRACE)
if _enabled and _env:
    try:
        enable_sink(_env)
    except (ValueError, OSError):
        pass
