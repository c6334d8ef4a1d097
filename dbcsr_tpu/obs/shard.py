"""The ONE per-process JSONL sharding contract for obs sinks.

Three streaming sinks persist per-process shards under a shared BASE
path — the span tracer (``DBCSR_TPU_TRACE``), the event bus
(``DBCSR_TPU_EVENTS``) and the telemetry time-series store
(``DBCSR_TPU_TS``).  They used to carry three copies of the same
delicate logic; this module is the single implementation they all
call:

* `shard_path(base, index)` — ``t.jsonl`` + 0 -> ``t.p0.jsonl`` (the
  extension stays last so shell globs like ``t.p*.jsonl`` work).
* `provisional_tag()` — the collision-proof ``tmp{host}-{pid}`` tag a
  shard opens under when the process index is not yet knowable
  (env activation runs before any backend exists).  Hostname + OS pid:
  multihost processes on a SHARED filesystem can collide on pid alone.
* `process_index()` — the jax process index IF a backend is already
  initialized, None otherwise; never forces backend init (in
  multi-process runs it races `jax.distributed.initialize`).
* `settle(base, path, fh, index)` — move a provisionally-named shard
  onto its final ``p{index}`` name: closes the stream, APPENDS onto an
  existing final shard instead of clobbering it (a rename must never
  destroy another session's data), renames otherwise, reopens for
  append.  On any OSError (cross-device, locked) the provisional shard
  is kept and reopened — data loss is never an option.

`parallel.multihost.init_multihost` drives the rebind for all three
sinks once the world's index is known.  Stdlib-only by contract: the
tracer imports this at module level.
"""

from __future__ import annotations

import os
import re


def shard_path(base: str, index) -> str:
    """Shard file for a base path: ``t.jsonl`` + 0 -> ``t.p0.jsonl``."""
    root, ext = os.path.splitext(base)
    return f"{root}.p{index}{ext}"


def provisional_tag() -> str:
    """Collision-proof provisional shard tag (``tmp{host}-{pid}``)."""
    import socket

    host = re.sub(r"[^A-Za-z0-9]+", "-", socket.gethostname())[:24] or "host"
    return f"tmp{host}-{os.getpid()}"


def process_index() -> int | None:
    """jax process index when a backend is ALREADY initialized; None
    otherwise (best-effort peek at xla_bridge's backend cache — never
    forces one)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return None  # no backend up yet: do NOT force one
    try:
        return int(jax.process_index())
    except Exception:
        return None


def expand_family(base: str) -> list:
    """The READ side of the contract: resolve a shard base (or a
    concrete file/glob) to its family's files.  A base like
    ``t.jsonl`` expands to ``t.p*.jsonl`` with unsettled ``.ptmp*``
    shards skipped (a run killed before its index resolved); a
    concrete path — even a provisional one — stays itself."""
    import glob

    hits = sorted(glob.glob(base))
    if not hits and not re.search(r"\.p\d+\.", os.path.basename(base)):
        root, ext = os.path.splitext(base)
        hits = [h for h in sorted(glob.glob(f"{root}.p*{ext}"))
                if ".ptmp" not in os.path.basename(h)]
    if not hits and os.path.exists(base):
        hits = [base]
    return hits


def settle(base: str, path: str, fh, index: int) -> tuple:
    """Move shard ``path`` (open stream ``fh``, may be None) onto its
    final ``shard_path(base, index)`` name.

    Returns ``(new_path, new_fh)`` — the final path and a re-opened
    append stream (or ``(path, fh)`` unchanged when the shard already
    sits at its final name).  Appends onto an existing final shard
    instead of replacing it; keeps the provisional shard on OSError.
    """
    new_path = shard_path(base, int(index))
    if new_path == path:
        return path, fh
    if fh is not None:
        fh.close()
        fh = None
    try:
        if os.path.exists(new_path):
            # a shard already lives at the final name (an earlier
            # run's, or another process's): APPEND this session's
            # records instead of clobbering it
            with open(path) as src, open(new_path, "a") as dst:
                dst.write(src.read())
            os.remove(path)
        else:
            os.replace(path, new_path)
    except OSError:  # cross-device/locked: keep the provisional shard
        new_path = path
    return new_path, open(new_path, "a")
