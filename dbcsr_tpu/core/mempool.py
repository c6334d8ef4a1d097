"""Device memory pool, chain ownership, and persistent device mirrors.

The analog of the reference's data-area memory pools
(`dbcsr_mem_methods.F`: `dbcsr_mempool_get`/`dbcsr_mempool_add` over
`dbcsr_memtype_type` areas, `dbcsr_data_types.F:86-114`): repeated
multiplies in an iterative workload (McWeeny purification, Newton–
Schulz sign/invsqrt) should never re-allocate device storage or
re-stage index arrays the previous iteration already placed on device.

Three cooperating mechanisms, all env-gated by ``DBCSR_TPU_POOL``:

* **The buffer pool** (`zeros`/`release`): freed bin buffers are kept
  keyed by (shape, dtype) and recycled through a donated
  ``zeros_like`` program, so XLA writes zeros INTO the retired buffer
  instead of allocating a new one — the jax realization of
  `dbcsr_mempool_get`.  A byte budget (``DBCSR_TPU_POOL_BYTES``) bounds
  held memory; releases beyond it are dropped (eviction), and
  high-water accounting feeds `obs.metrics`.
* **Chain ownership** (`chain`): a context manager that adopts every
  matrix created inside it.  Adopted matrices may donate replaced bin
  buffers back to the pool from the structure-mutation funnels
  (`BlockSparseMatrix.set_structure_from_device` / `map_bin_data`) and
  are freed wholesale when retired or when the chain exits — the
  `dbcsr_release` discipline of the reference's work-matrix lifecycle,
  made explicit.  `BlockSparseMatrix.copy` marks both sides shared,
  which permanently disables donation for those buffers (conservative:
  a shared buffer must never be recycled).
* **Device index mirrors** (`upload_index`): a content-keyed LRU of
  host->device uploads of gather/scatter index arrays (the
  ``jnp.asarray`` calls scattered through the engine).  A
  structure-stable chain uploads each index array once; later
  iterations hit the mirror even when the owning matrices are fresh
  temporaries.  Complemented by `BlockSparseMatrix.device_index`
  (per-matrix mirrors invalidated when the pattern fingerprint
  changes, i.e. on any finalize that alters structure).

H2D/D2H accounting: `record_h2d`/`record_d2h` feed the
``dbcsr_tpu_{h2d,d2h}_bytes_total`` counters and cheap module totals
(`transfer_totals`), instrumented at the engine's staging choke points
— the per-iteration "restage bytes" signal the chained-workload bench
gates on (bytes collapse to ~zero after iteration 1).
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dbcsr_tpu.utils import lockcheck as _lockcheck  # noqa: E402
from dbcsr_tpu.utils.rounding import bucket_pow2

_lock = _lockcheck.wrap("core.mempool", threading.RLock())

# --------------------------------------------------------------- enable

_enabled = os.environ.get("DBCSR_TPU_POOL", "1") not in ("0", "false", "no")


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Programmatic pool/mirror toggle (the bench A/B's unpooled
    control); disabling does not drop already-held buffers — call
    `clear()` for a cold start."""
    global _enabled
    _enabled = bool(on)


def _budget_bytes() -> int:
    try:
        return int(os.environ.get("DBCSR_TPU_POOL_BYTES", str(2 << 30)))
    except ValueError:
        return 2 << 30


# ----------------------------------------------------------- accounting

# module totals are the authoritative cheap stats (metrics counters are
# refreshed alongside so scrapes and snapshots agree)
_stats = {
    "hits": 0, "misses": 0, "returns": 0, "evictions": 0,
    "bytes_held": 0, "high_water": 0, "h2d_bytes": 0, "d2h_bytes": 0,
}

_metric_cache: dict = {}


def _metric(name: str, help: str):
    m = _metric_cache.get(name)
    if m is None:
        from dbcsr_tpu.obs import metrics as _metrics

        m = _metric_cache[name] = _metrics.counter(name, help)
    return m


def _bump(kind: str, n: float = 1) -> None:
    _stats[kind] += n
    _metric(
        f"dbcsr_tpu_pool_{kind}_total",
        "device memory pool events by kind (checkout hits/misses, "
        "buffer returns, budget evictions)",
    ).inc(n)


def _held_gauge(v: int) -> None:
    from dbcsr_tpu.obs import metrics as _metrics

    _metrics.gauge(
        "dbcsr_tpu_pool_bytes_held",
        "device bytes currently held by the memory pool free lists",
    ).set(v)


def record_h2d(nbytes: int) -> None:
    """Count one host->device staging transfer (block data or index
    uploads) — the restage-bytes signal of the chained-workload bench."""
    if nbytes:
        _stats["h2d_bytes"] += int(nbytes)
        _metric("dbcsr_tpu_h2d_bytes_total",
                "host->device bytes staged (block data + index uploads)"
                ).inc(int(nbytes))


def record_d2h(nbytes: int) -> None:
    """Count one device->host fetch (block reads, host-driver C
    round-trips)."""
    if nbytes:
        _stats["d2h_bytes"] += int(nbytes)
        _metric("dbcsr_tpu_d2h_bytes_total",
                "device->host bytes fetched (block reads + host-driver "
                "round-trips)").inc(int(nbytes))


def transfer_totals() -> dict:
    """{"h2d": bytes, "d2h": bytes} since the last `reset_stats`."""
    return {"h2d": _stats["h2d_bytes"], "d2h": _stats["d2h_bytes"]}


def pool_stats() -> dict:
    """Machine-readable pool state for `obs.metrics.snapshot()`."""
    with _lock:
        return {
            "enabled": _enabled,
            "hits": _stats["hits"],
            "misses": _stats["misses"],
            "returns": _stats["returns"],
            "evictions": _stats["evictions"],
            "bytes_held": _stats["bytes_held"],
            "high_water": _stats["high_water"],
            "budget_bytes": _budget_bytes(),
            "buckets": len(_free),
            "mirror_entries": len(_mirror),
            "mirror_bytes": _mirror_bytes,
            "h2d_bytes": _stats["h2d_bytes"],
            "d2h_bytes": _stats["d2h_bytes"],
        }


def reset_stats() -> None:
    """Zero the counters/totals (paired with `obs.metrics.reset`);
    held buffers and mirrors survive — use `clear()` to drop them."""
    with _lock:
        for k in _stats:
            _stats[k] = 0
        _stats["bytes_held"] = sum(
            sum(_arr_bytes(a) for a in lst) for lst in _free.values())
        _stats["high_water"] = _stats["bytes_held"]
        _metric_cache.clear()


# ------------------------------------------------------------ free lists

# (shape, dtype str) -> [retired device arrays]
_free: dict = {}


def _arr_bytes(a) -> int:
    return int(np.prod(a.shape)) * int(jnp.dtype(a.dtype).itemsize)


# donated zeros_like: XLA writes zeros INTO the retired buffer — the
# checkout path's allocation-free rezero (one tiny specialization per
# (shape, dtype), reused for the life of the process)
_rezero = jax.jit(jnp.zeros_like, donate_argnums=0)


def zeros(shape, dtype):
    """A zeroed device array of ``shape``/``dtype`` — recycled from the
    pool when a retired buffer of the exact (shape, dtype) is held
    (checkout hit), freshly allocated otherwise (miss).  Checkout is
    always safe: pooled buffers are exclusively owned by the pool."""
    shape = tuple(int(s) for s in shape)
    dt = jnp.dtype(dtype)
    if not _enabled:
        return jnp.zeros(shape, dt)
    key = (shape, str(dt))
    buf = None
    with _lock:
        lst = _free.get(key)
        while lst:
            cand = lst.pop()
            if not lst:
                _free.pop(key, None)
            _stats["bytes_held"] -= _arr_bytes(cand)
            if not cand.is_deleted():
                buf = cand
                break
        _bump("hits" if buf is not None else "misses")
        # refresh the gauge on BOTH outcomes: a miss that skipped
        # deleted entries changed bytes_held too
        _held_gauge(_stats["bytes_held"])
    if buf is None:
        return jnp.zeros(shape, dt)
    try:
        return run_donated(_rezero, buf)
    except Exception:  # backend refused the donation: fall back fresh
        return jnp.zeros(shape, dt)


def run_donated(fn, *args, **kwargs):
    """Invoke a donating jitted callable with the donated-buffer trace
    warning silenced: a backend that declines the aliasing (CPU XLA
    often does for ``zeros_like``-style programs) still computes the
    same values — the warning is per-specialization noise, and this is
    the ONE place the suppression pattern lives."""
    import warnings

    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn(*args, **kwargs)


def release(arr) -> bool:
    """Return a device buffer to the pool.  OWNERSHIP CONTRACT: the
    caller asserts no other live reference will ever read ``arr``
    again — the next checkout donates the buffer, which invalidates
    every stale reference (a later read raises, it never reads
    recycled data).  Returns True when the buffer was banked."""
    if not _enabled:
        return False
    if not isinstance(arr, jax.Array):
        return False
    try:
        if arr.is_deleted() or not arr.is_fully_addressable:
            return False
        if len(arr.devices()) != 1:
            return False  # sharded arrays are never pool candidates
    except Exception:
        return False
    nbytes = _arr_bytes(arr)
    with _lock:
        budget = _budget_bytes()
        if nbytes > budget:
            _bump("evictions")  # can never fit: drop the incoming buffer
            return False
        # over budget: evict the OLDEST held buffers (oldest free-list
        # keys first — dict insertion order approximates LRU by shape)
        # so a workload phase change reclaims dead shapes instead of
        # wedging the pool full of buffers nothing checks out anymore
        while _stats["bytes_held"] + nbytes > budget and _free:
            k0 = next(iter(_free))
            lst0 = _free[k0]
            old = lst0.pop(0)
            if not lst0:
                del _free[k0]
            _stats["bytes_held"] -= _arr_bytes(old)
            _bump("evictions")
        key = (tuple(int(s) for s in arr.shape), str(jnp.dtype(arr.dtype)))
        _free.setdefault(key, []).append(arr)
        _stats["bytes_held"] += nbytes
        _stats["high_water"] = max(_stats["high_water"],
                                   _stats["bytes_held"])
        _bump("returns")
        _held_gauge(_stats["bytes_held"])
    return True


def clear() -> None:
    """Drop every held buffer and mirror entry (tests / OOM pressure)."""
    global _mirror_bytes
    with _lock:
        _free.clear()
        _mirror.clear()
        _mirror_bytes = 0
        _stats["bytes_held"] = 0
        _held_gauge(0)


# ---------------------------------------------------------- index mirror

# content-keyed LRU of device uploads: (tag, shape, dtype, sha1(bytes))
# -> device array.  Ordered dict emulation via insertion + move.
from collections import OrderedDict as _OrderedDict  # noqa: E402

_mirror: "_OrderedDict[tuple, object]" = _OrderedDict()
_mirror_bytes = 0
_MIRROR_MAX_ENTRIES = 512
_MIRROR_MAX_BYTES = 128 * 1024 * 1024


def upload_index(tag: str, arr) -> object:
    """Device copy of a host index array, cached by CONTENT — the
    persistent device mirror of the engine's per-op ``jnp.asarray``
    staging (`acc_devmem` + `acc_ready` analog): a structure-stable
    chain uploads each gather/scatter index once, and every later
    iteration (even through fresh temporary matrices) hits the mirror.
    Staleness is impossible by construction (the key embeds the
    bytes); the LRU is bounded by entries AND bytes.  Cached arrays
    are shared and never donated."""
    arr = np.ascontiguousarray(arr)
    if not _enabled:
        record_h2d(arr.nbytes)
        return jnp.asarray(arr)
    key = (tag, arr.shape, str(arr.dtype),
           hashlib.sha1(arr.tobytes()).digest())
    global _mirror_bytes
    with _lock:
        hit = _mirror.get(key)
        if hit is not None and not hit.is_deleted():
            _mirror.move_to_end(key)
            return hit
    dev = jnp.asarray(arr)
    record_h2d(arr.nbytes)
    with _lock:
        if key not in _mirror:
            _mirror[key] = dev
            _mirror_bytes += _arr_bytes(dev)
            while _mirror and (len(_mirror) > _MIRROR_MAX_ENTRIES
                               or _mirror_bytes > _MIRROR_MAX_BYTES):
                _, old = _mirror.popitem(last=False)
                _mirror_bytes -= _arr_bytes(old)
    return dev


def upload_index_bucketed(tag: str, arr, fill: int) -> object:
    """`upload_index` of ``arr`` filled up with ``fill`` to the
    `bucket_pow2` of its length: a program that takes it is keyed by
    the bucket and not by the count, so a pattern that moves (a
    tensor's next batch) reuses it.  ``fill`` is an id the program
    ignores: past the end of a scatter's destination (dropped), or any
    valid row of a gather whose result lands there."""
    arr = np.ascontiguousarray(arr)
    pad = bucket_pow2(len(arr)) - len(arr)
    if pad > 0:
        arr = np.concatenate([arr, np.full(pad, fill, arr.dtype)])
    return upload_index(tag, arr)


def alias_bins(m) -> tuple:
    """Zero-copy result snapshot of ``m``'s bins: ``([(shape, data,
    count)], total_device_bytes)``.  The snapshot ALIASES the live
    buffers — the caller must mark the matrix's bins shared
    (``m._bins_shared = True``) so no funnel ever donates them back to
    the pool, and must never bank the aliased buffers itself
    (exclusivity is unprovable; eviction just drops the references).
    Shared by the incremental-multiply result cache and the serve
    product cache."""
    bins = [(b.shape, b.data, b.count) for b in m.bins]
    return bins, sum(_arr_bytes(d) for _, d, _ in bins)


def adopt_aliased_bins(m, keys, bins_snapshot) -> None:
    """Install an `alias_bins` snapshot into ``m`` wholesale: the
    matrix adopts the ALIASED device buffers and its bins are marked
    shared so no later funnel can donate them while the snapshot's
    holder (the incremental result cache, the serve product cache)
    still references them.  The one adoption implementation both
    caches share."""
    from dbcsr_tpu.core.matrix import _Bin

    m.set_structure_from_device(
        np.ascontiguousarray(keys, np.int64).copy(),
        [_Bin(shape, data, count) for shape, data, count in bins_snapshot])
    m._bins_shared = True


# ----------------------------------------------------------- snapshots

class SnapshotError(RuntimeError):
    """Structured checkpoint/rollback contract violation (e.g. restoring
    a snapshot whose matrix was already retired to the pool)."""


class MatrixSnapshot:
    """A pooled, device-resident point-in-time checkpoint of one
    matrix: host index arrays plus fresh device copies of every bin
    buffer.  Built by `snapshot_matrix` / `chain.snapshot`, applied by
    `restore_matrix` / `chain.restore`.  The snapshot owns its copies
    exclusively (never aliased into the matrix), so it stays valid
    across any later mutation, donation, or failure of the source —
    and one snapshot can be restored more than once (each restore
    installs fresh copies)."""

    __slots__ = ("matrix", "keys", "row_ptr", "ent_bin", "ent_slot",
                 "bins", "valid", "chain_owner")

    def __init__(self, m, chain_owner: Optional["chain"] = None):
        import jax.numpy as _jnp

        self.matrix = m
        self.keys = m.keys.copy()
        self.row_ptr = m.row_ptr.copy()
        self.ent_bin = m.ent_bin.copy()
        self.ent_slot = m.ent_slot.copy()
        self.bins = [(b.shape, _jnp.array(b.data, copy=True), b.count)
                     for b in m.bins]
        self.valid = m.valid
        self.chain_owner = chain_owner

    def nbytes(self) -> int:
        return sum(_arr_bytes(d) for _, d, _ in self.bins)


def snapshot_matrix(m, chain_owner: Optional["chain"] = None
                    ) -> MatrixSnapshot:
    """Checkpoint ``m``'s structure and device data (see
    `MatrixSnapshot`)."""
    return MatrixSnapshot(m, chain_owner=chain_owner)


def restore_matrix(snap: MatrixSnapshot):
    """Roll ``snap.matrix`` back to the snapshotted state: structure
    fields replaced, bins rebuilt from FRESH copies of the snapshot's
    device data (the snapshot stays reusable).  The replaced bin
    buffers are donated back to the pool only when the matrix owns
    them exclusively — `copy()`-shared bins are NEVER restored via
    donation (the other side still reads them).  Returns the matrix."""
    from dbcsr_tpu.core.matrix import _Bin

    import jax.numpy as _jnp

    m = snap.matrix
    donatable = m._donatable  # decided on the PRE-restore aliasing
    old_data = [b.data for b in m.bins] if donatable else None
    m.keys = snap.keys.copy()
    m.row_ptr = snap.row_ptr.copy()
    m.ent_bin = snap.ent_bin.copy()
    m.ent_slot = snap.ent_slot.copy()
    m.bins = [_Bin(shape, _jnp.array(data, copy=True), count)
              for shape, data, count in snap.bins]
    m._shape_to_bin = {b.shape: i for i, b in enumerate(m.bins)}
    m._work.clear()
    m._work_batches.clear()
    m.invalidate_dense_cache()
    m._bins_shared = False  # restored bins are exclusively owned again
    # the epoch stays MONOTONE through a rollback and marks everything
    # dirty: a consumer that cached a result computed from the
    # now-discarded post-snapshot state must never see "unchanged" —
    # a rolled-back matrix is never served as current
    m._note_mutation(None)
    m.valid = snap.valid
    if old_data is not None:
        for d in old_data:
            release(d)
    return m


# -------------------------------------------------------------- chains

# per-THREAD chain stack: the obs server (and the roadmap's concurrent
# serving direction) run worker threads — a chain entered on one thread
# must never adopt (and later free) matrices another thread is building
_chain_tls = threading.local()


def _stack() -> list:
    st = getattr(_chain_tls, "stack", None)
    if st is None:
        st = _chain_tls.stack = []
    return st


def current_chain() -> Optional["chain"]:
    """The innermost chain active ON THIS THREAD, or None."""
    st = _stack()
    return st[-1] if st else None


class chain:
    """Scope of device-resident matrix state: matrices created inside
    the ``with`` block are ADOPTED (pool-owned) — their structure
    mutations donate replaced bin buffers back to the pool, and
    whatever is still adopted when the block exits is freed wholesale.

    * ``retire(m)`` — free an adopted intermediate NOW (its buffers
      feed the next iteration's checkouts);
    * ``detach(m)`` — let a result escape the scope: transferred to
      the enclosing chain when one is active, otherwise it keeps pool
      ownership but is never freed by this chain.

    The pattern (`models/purify.py` et al.)::

        with chain() as ch:
            cur = p0
            for _ in range(steps):
                new = step(cur)          # temporaries auto-adopted
                if cur is not p0:
                    ch.retire(cur)       # buffers -> pool
                cur = new
            ch.detach(cur)
        return cur
    """

    def __init__(self):
        self._adopted: dict = {}  # id(matrix) -> matrix
        # retirement is stamped ON the matrix object (_chain_retired),
        # never tracked as a raw id: a retired matrix's id is eligible
        # for CPython reuse the moment the last reference drops, and a
        # stale id in a set would make `restore` spuriously reject a
        # LEGITIMATE rollback of a later same-address matrix.  Every
        # restorable snapshot holds a strong reference to its matrix,
        # so the attribute is always authoritative.

    def __enter__(self) -> "chain":
        _stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            _stack().remove(self)
        except ValueError:
            pass
        for m in list(self._adopted.values()):
            try:
                m.free()
            except Exception:
                pass  # a half-built matrix mid-fault: never mask the error
        self._adopted.clear()
        return False

    def adopt(self, m) -> object:
        """Mark ``m`` pool-owned and track it for end-of-chain free."""
        m._pool_owned = True
        self._adopted[id(m)] = m
        return m

    def retire(self, m) -> None:
        """Free an adopted matrix now, returning its bins to the pool.
        A no-op for matrices this chain does not own (a caller-provided
        input is never freed)."""
        tracked = self._adopted.pop(id(m), None)
        if tracked is not None:
            tracked._chain_retired = True
            tracked.free()

    def snapshot(self, m) -> MatrixSnapshot:
        """Pooled, device-resident checkpoint of ``m`` (any matrix —
        chain-owned or a caller input), restorable through
        `chain.restore`.  The rollback half of the chain-integrity
        contract: models checkpoint the accepted iterate before a step
        and roll back instead of iterating on a corrupted one
        (docs/resilience.md § Chain checkpoint/rollback)."""
        return snapshot_matrix(m, chain_owner=self)

    def restore(self, snap: MatrixSnapshot):
        """Roll the snapshotted matrix back to its checkpoint.

        Structured errors instead of silent corruption: restoring a
        matrix that was `retire`d after the snapshot raises
        `SnapshotError` (its buffers are pool property now).  Ownership
        is NEVER changed by a restore — a matrix adopted by an outer
        chain stays the outer chain's to free, whichever (nested) chain
        performs the restore; `copy()`-shared bins are never donated by
        the restore (see `restore_matrix`)."""
        if getattr(snap.matrix, "_chain_retired", False):
            raise SnapshotError(
                f"cannot restore {snap.matrix.name!r}: the matrix was "
                f"retired after the snapshot (its buffers belong to "
                f"the pool; take the snapshot before retiring, or "
                f"defer the retire until the iterate is validated)")
        return restore_matrix(snap)

    def scope(self):
        """Context manager for one split/iteration of a loop running
        inside this chain: matrices ADOPTED while the scope is open
        (engine temporaries — desymmetrized operands, transposes,
        remapped tensors) are retired at its exit, feeding the next
        split's checkouts, unless they were already retired or
        detached.  Matrices created before the scope (the caller's
        operands and C) are untouched — the ownership check in
        `retire` makes over-retiring impossible."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            before = set(self._adopted)
            try:
                yield self
            finally:
                for key in [k for k in self._adopted if k not in before]:
                    m = self._adopted.pop(key, None)
                    if m is not None:
                        m._chain_retired = True
                        try:
                            m.free()
                        except Exception:
                            pass  # a half-built temporary mid-fault
        return _scope()

    def detach(self, m) -> object:
        """Release ``m`` from this chain's end-of-scope free.  With an
        enclosing chain active the matrix transfers to it (nested
        step/iteration scopes); otherwise it escapes with pool
        ownership intact (still donates on later mutations, never
        auto-freed)."""
        if self._adopted.pop(id(m), None) is None:
            # never ours (e.g. the caller's input threaded straight
            # through a zero-iteration loop): detach must not grant
            # ownership — an enclosing chain would otherwise FREE the
            # caller's matrix at its exit
            return m
        # the enclosing chain is the one UNDER self on the stack
        # (detach runs inside the with block, so self is the top)
        parent = None
        st = _stack()
        if self in st:
            i = st.index(self)
            parent = st[i - 1] if i > 0 else None
        elif st:
            parent = st[-1]
        if parent is not None:
            parent.adopt(m)
        return m
