"""Delta-aware incremental multiply: recompute only what changed.

DBCSR's life is SCF loops — long sequences of ``C := alpha * A @ B``
products whose operands change *slightly* per iteration.  The plan
cache already makes the HOST side of a repeated product free; this
module extends reuse to the VALUES: when a product's plan cache hits
and its operands carry a known dirty-block delta since the last
execution of the same (A, B, scalars, flags) product (the mutation
journal of `core.matrix.BlockSparseMatrix`), only the C blocks whose
accumulation reads a dirty A/B block are recomputed — the rest splice
from the cached device-resident result.

**Bitwise identity by construction**: a C block's accumulation
sequence is its candidate triples sorted by (C block, A entry),
independent of every other C block; the subset run keeps exactly that
per-block sequence (chunking at a different ``mm_stack_size`` boundary
only splits the same ordered scatter-adds — the coalescer's
established contract), and spliced blocks are the previous result's
bits, which unchanged inputs would reproduce.

**Safety ladder** (every rung falls back to full recompute, never to
a wrong answer):

* unknown delta (structure change, journal truncation, rolled-back
  epoch, different operand objects) -> full recompute;
* ABFT live on the recomputed launches like any stack run, plus —
  when the ABFT knob is on — a full-product probe over the assembled
  (spliced) C; a mismatch discards the splice and recomputes fully;
* the ``incremental`` fault site makes the splice injectable
  (`resilience.faults`: raise/oom abort the splice, nan/flip corrupt
  it for the probe to catch);
* repeated probe/fault failures open a breaker-style degrade: the
  plane disables itself for the process (``incremental_degrade`` on
  the event bus) instead of flapping.

Result snapshots are ZERO-COPY: the cache aliases the product's final
bin buffers and marks C's bins shared (`_bins_shared`), which
permanently blocks pool donation of those buffers — the chain-owned
residency contract extended to a cross-product cache.  Eviction drops
the references (device memory frees when the last holder lets go);
entries are never banked back into the pool because exclusivity
cannot be proven.

Kill switch: ``DBCSR_TPU_INCREMENTAL=auto|off|full`` (config
``incremental``).  ``off`` removes every hook; ``full`` keeps the
tracking + cache maintenance but always recomputes — the honest A/B
control leg that still pays the bookkeeping.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import numpy as np

from dbcsr_tpu.core import digests, mempool

_CACHE_MAX_ENTRIES = 8
_CACHE_MAX_BYTES = 512 * 1024 * 1024
# recomputing almost everything pays splice overhead for ~no savings
_MAX_RECOMPUTE_FRACTION = 0.95
_BREAKER_THRESHOLD = 3  # consecutive probe/fault failures before degrade


class _Entry:
    """One cached product result: the (A, B) operand identities and
    epochs the result is valid against, plus the result's structure
    and ALIASED device bin buffers (held here, shared-marked on C).
    Operands are held by WEAK reference — they exist only for the
    ``is``-identity check, and a strong reference would pin both full
    operand matrices (outside the byte budget, which counts only C's
    bins) for the entry's lifetime."""

    __slots__ = ("a", "b", "a_epoch", "b_epoch", "keys", "bins", "nbytes")

    def __init__(self, a, b, c):
        import weakref

        self.a = weakref.ref(a)
        self.b = weakref.ref(b)
        self.a_epoch = a.mutation_epoch
        self.b_epoch = b.mutation_epoch
        self.keys = c.keys
        self.bins, self.nbytes = mempool.alias_bins(c)


_cache: "OrderedDict[tuple, _Entry]" = OrderedDict()
_cache_bytes = 0
# plan keys executed once (with the operand ids): a key seen twice with
# the SAME operands starts caching — one-shot products never pay the
# snapshot bookkeeping
_seen: "OrderedDict[tuple, tuple]" = OrderedDict()
_SEEN_MAX = 64

_breaker = {"failures": 0, "open": False}

# cumulative reuse totals (cheap module ints; the models' per-iteration
# reuse-fraction events diff these through `stats_snapshot`)
_totals = {
    "products": 0, "reused_blocks": 0, "recomputed_blocks": 0,
    "saved_flops": 0, "fallbacks": 0,
}


def _counter(result: str) -> None:
    from dbcsr_tpu.obs import metrics as _metrics

    _metrics.counter(
        "dbcsr_tpu_incremental_total",
        "delta-aware incremental multiply outcomes (hit_splice = partial "
        "recompute + splice, hit_unchanged = zero-delta full reuse, "
        "fallback_* = full recompute with the named reason)",
    ).inc(result=result)


def mode() -> str:
    from dbcsr_tpu.core.config import get_config

    return get_config().incremental


def _key(plan_key, alpha) -> tuple:
    return (plan_key, digests.scalar_key(alpha))


def _drop(key) -> None:
    global _cache_bytes
    ent = _cache.pop(key, None)
    if ent is not None:
        _cache_bytes -= ent.nbytes


def note_format_executed(a, b) -> None:
    """A dense canvas-path execution just restructured C for these
    operands: cached delta entries keyed to them can never be reused
    again under a stack plan built for the SAME product state (the
    format planner may flip back after a config change), so drop them
    eagerly instead of waiting for the epoch check to churn through
    stale entries."""
    stale = [k for k, ent in _cache.items()
             if ent.a() is a or ent.b() is b]
    for k in stale:
        _drop(k)


def reset() -> None:
    """Drop every cached result and close the breaker (tests)."""
    global _cache_bytes
    _cache.clear()
    _seen.clear()
    _cache_bytes = 0
    _breaker["failures"] = 0
    _breaker["open"] = False
    for k in _totals:
        _totals[k] = 0


def stats_snapshot() -> dict:
    """Cumulative reuse totals (copy) — diff two snapshots for a
    per-phase reuse fraction (`reuse_delta`)."""
    return dict(_totals)


def reuse_delta(prev: dict) -> dict:
    """Per-interval reuse summary between a `stats_snapshot` and now:
    blocks reused/recomputed, saved flops, and the reuse fraction
    (0.0 when the interval ran no delta-eligible products)."""
    reused = _totals["reused_blocks"] - prev.get("reused_blocks", 0)
    recomputed = _totals["recomputed_blocks"] - prev.get(
        "recomputed_blocks", 0)
    total = reused + recomputed
    return {
        "products": _totals["products"] - prev.get("products", 0),
        "reused_blocks": int(reused),
        "recomputed_blocks": int(recomputed),
        "saved_flops": int(_totals["saved_flops"]
                           - prev.get("saved_flops", 0)),
        "reuse_fraction": round(reused / total, 6) if total else 0.0,
    }


def _breaker_trip(reason: str) -> None:
    from dbcsr_tpu.obs import events as _events
    from dbcsr_tpu.obs import metrics as _metrics

    _totals["fallbacks"] += 1
    _breaker["failures"] += 1
    if _breaker["failures"] >= _BREAKER_THRESHOLD and not _breaker["open"]:
        _breaker["open"] = True
        _metrics.counter(
            "dbcsr_tpu_incremental_degrade_total",
            "incremental plane breaker opens (consecutive probe/fault "
            "failures; the plane degrades to full recompute)",
        ).inc()
        _events.publish("incremental_degrade", {
            "reason": reason, "failures": _breaker["failures"]})


def _dirty_entry_mask(m, dirty_keys) -> Optional[np.ndarray]:
    """Boolean mask over ``m``'s entries whose block key is in
    ``dirty_keys``; None when a dirty key is not a stored entry (the
    journal refers to structure this index no longer has — treat the
    delta as unknown)."""
    mask = np.zeros(len(m.keys), bool)
    if not len(dirty_keys):
        return mask
    if not len(m.keys):
        return None  # dirty keys against an empty index: unknown
    pos = np.searchsorted(m.keys, dirty_keys)
    pos_c = np.minimum(pos, len(m.keys) - 1)
    if not bool(np.all(m.keys[pos_c] == dirty_keys)):
        return None
    mask[pos_c] = True
    return mask


def maybe_reuse(plan_key, a, b, c, alpha, new_keys, cand_keys, a_ent,
                b_ent) -> Optional[int]:
    """Attempt the delta-aware path for one eligible product (the
    caller has already verified: stack path, beta == 0, no limits or
    window, unfiltered, non-symmetric, plan-cacheable).  Returns the
    executed true flops on success, None for a full recompute."""
    md = mode()
    if md == "off":
        return None
    key = _key(plan_key, alpha)
    ent = _cache.get(key)
    if md == "full":
        if ent is not None:
            _counter("forced_full")
        return None
    if _breaker["open"]:
        if ent is not None:
            _counter("fallback_degraded")
        return None
    if ent is None:
        _counter("miss")
        return None
    if ent.a() is not a or ent.b() is not b:
        _counter("fallback_identity")
        _drop(key)
        return None
    dirty_a = a.dirty_keys_since(ent.a_epoch)
    dirty_b = b.dirty_keys_since(ent.b_epoch)
    if dirty_a is None or dirty_b is None:
        _counter("fallback_epoch")
        _drop(key)
        return None
    if len(new_keys) != len(ent.keys) or not np.array_equal(
            new_keys, ent.keys):
        # C entered with a different pattern: the union pattern moved
        _counter("fallback_structure")
        _drop(key)
        return None
    amask = _dirty_entry_mask(a, dirty_a)
    bmask = _dirty_entry_mask(b, dirty_b)
    if amask is None or bmask is None:
        _counter("fallback_epoch")
        _drop(key)
        return None

    from dbcsr_tpu.mm import multiply as _mm
    from dbcsr_tpu.obs import flight as _flight

    ntrip = len(cand_keys)
    if amask.any() or bmask.any():
        trip_dirty = amask[a_ent] | bmask[b_ent]
        affected = np.unique(cand_keys[trip_dirty])
        recompute = _mm.mask_in_sorted(cand_keys, affected)
    else:
        affected = np.empty(0, np.int64)
        recompute = np.zeros(ntrip, bool)
    n_rec = int(recompute.sum())
    if ntrip and n_rec / ntrip > _MAX_RECOMPUTE_FRACTION:
        _counter("fallback_all_dirty")
        return None  # entry refreshed by the full run's note_executed

    try:
        flops = _execute_splice(key, ent, a, b, c, alpha, new_keys,
                                cand_keys, a_ent, b_ent, recompute,
                                affected, plan_key)
    except _SpliceRejected as exc:
        _counter(exc.result)
        _breaker_trip(exc.result)
        return None
    _breaker["failures"] = 0
    _install(key, a, b, c)  # re-baseline on the just-assembled result
    n_reused = len(new_keys) - len(affected)
    _totals["products"] += 1
    _totals["reused_blocks"] += n_reused
    _totals["recomputed_blocks"] += len(affected)
    reuse_frac = n_reused / max(len(new_keys), 1)
    full_flops = _mm._true_product_flops(a, b)
    saved = max(0, full_flops - flops)
    _totals["saved_flops"] += saved
    from dbcsr_tpu.obs import metrics as _metrics

    _counter("hit_unchanged" if n_rec == 0 else "hit_splice")
    _metrics.counter(
        "dbcsr_tpu_incremental_saved_flops_total",
        "true flops avoided by delta-aware reuse (full product flops "
        "minus the recomputed subset's)",
    ).inc(saved)
    _metrics.counter(
        "dbcsr_tpu_incremental_saved_bytes_total",
        "device bytes of C blocks spliced from the cached result "
        "instead of recomputed",
    ).inc(_spliced_bytes(c, affected))
    _flight.note("incremental", "unchanged" if n_rec == 0 else "splice")
    _flight.note("reuse_fraction", round(reuse_frac, 4))
    return int(flops)


def _spliced_bytes(c, affected) -> int:
    """Exact device bytes of the C blocks served from the cache."""
    from dbcsr_tpu.mm.multiply import mask_in_sorted

    itemsize = int(np.dtype(c.dtype).itemsize)
    aff_mask = mask_in_sorted(c.keys, affected) if len(affected) else \
        np.zeros(len(c.keys), bool)
    total = 0
    for b_id, bin_ in enumerate(c.bins):
        sel = (c.ent_bin == b_id) & ~aff_mask
        total += int(sel.sum()) * bin_.shape[0] * bin_.shape[1] * itemsize
    return total


class _SpliceRejected(Exception):
    """Internal: the splice was aborted (fault, probe mismatch) and the
    caller must fall back to full recompute."""

    def __init__(self, result: str, cause: BaseException | None = None):
        super().__init__(result)
        self.result = result
        self.cause = cause


def _execute_splice(key, ent: _Entry, a, b, c, alpha, new_keys, cand_keys,
                    a_ent, b_ent, recompute, affected, plan_key) -> int:
    """Rebuild C (beta == 0 zeros), run ONLY the triples targeting
    affected C blocks (ABFT live on those launches like any stack
    run), splice every clean block from the cached result, then
    probe-verify the assembled product when the ABFT knob is on."""
    from dbcsr_tpu.acc import abft as _abft
    from dbcsr_tpu.mm import multiply as _mm
    from dbcsr_tpu.resilience import faults as _faults

    try:
        if _faults.active():
            _faults.maybe_inject("incremental", n=str(len(affected)))
        if not len(affected):
            # zero-delta repeat: adopt the cached bins wholesale (the
            # same `mempool.adopt_aliased_bins` the serve cache's
            # install uses) — no rebuild, no launches, no splice
            mempool.adopt_aliased_bins(c, ent.keys, ent.bins)
            flops = 0
        else:
            _mm._rebuild_c(c, new_keys, 0.0)
            sub_plan_key = plan_key + (
                "incremental", digests.index_digest(affected))
            flops = _mm._run_stacks(
                c, a, b, cand_keys[recompute], a_ent[recompute],
                b_ent[recompute], alpha, plan_key=sub_plan_key,
                c_zero=True)
            # splice clean blocks from the cached result (bin geometry
            # is identical: same keys -> same binning -> same buckets)
            aff_mask = _mm.mask_in_sorted(new_keys, affected)
            for b_id, bin_ in enumerate(c.bins):
                shape, cached, count = ent.bins[b_id]
                if shape != bin_.shape or count != bin_.count \
                        or cached.shape != bin_.data.shape:
                    raise _SpliceRejected("fallback_structure")
                sel = np.nonzero((c.ent_bin == b_id) & ~aff_mask)[0]
                if not len(sel):
                    continue
                # row-SELECT, not row-scatter: XLA-CPU lowers a
                # scatter as a serial per-row loop, which dominated
                # the splice on the bench; the where-select runs at
                # memory bandwidth.  The mask is content-stable across
                # an SCF loop's iterations (same dirty subset), so the
                # upload hits the index mirror.
                keep = np.zeros(bin_.data.shape[0], bool)
                keep[c.ent_slot[sel]] = True
                bin_.data = _splice(
                    bin_.data, cached,
                    mempool.upload_index("inc_keep", keep))
        if _faults.active():
            c.map_bin_data(lambda d: _faults.corrupt("incremental", d))
        if _abft.enabled():
            _abft.verify_product(a, b, c, alpha, 0.0, None)
        return flops
    except _SpliceRejected:
        raise
    except _abft.AbftMismatchError as exc:
        _abft.record_recovery("incremental")
        raise _SpliceRejected("fallback_abft", exc) from exc
    except Exception as exc:
        raise _SpliceRejected("fallback_fault", exc) from exc


def note_executed(plan_key, a, b, c, alpha) -> None:
    """Record a fully executed eligible product: the first sighting of
    a (plan, operands) pair only marks it seen; a repeat installs the
    zero-copy result snapshot (aliasing C's final bins, which are
    marked shared so the pool never recycles them under the cache)."""
    global _cache_bytes
    md = mode()
    if md == "off":
        return
    key = _key(plan_key, alpha)
    ids = (id(a), id(b))
    if key not in _cache and _seen.get(key) != ids:
        _seen[key] = ids
        _seen.move_to_end(key)
        while len(_seen) > _SEEN_MAX:
            _seen.popitem(last=False)
        return
    _install(key, a, b, c)


def _install(key, a, b, c) -> None:
    global _cache_bytes
    old = _cache.pop(key, None)
    if old is not None:
        _cache_bytes -= old.nbytes
    ent = _Entry(a, b, c)
    c._bins_shared = True  # the cache aliases these buffers: no donation
    _cache[key] = ent
    _cache_bytes += ent.nbytes
    while _cache and (len(_cache) > _CACHE_MAX_ENTRIES
                      or _cache_bytes > _CACHE_MAX_BYTES):
        if len(_cache) == 1 and _cache_bytes <= _CACHE_MAX_BYTES:
            break
        _, evicted = _cache.popitem(last=False)
        _cache_bytes -= evicted.nbytes


_splice_jit = None  # built on first use (keeps module import jax-light)


def _splice(computed, cached, keep_mask):
    """Per-row select: cached rows where ``keep_mask``, freshly
    computed rows elsewhere; the computed buffer is donated (the
    spliced output replaces it in C)."""
    global _splice_jit
    if _splice_jit is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, donate_argnums=0)
        def _impl(computed, cached, keep_mask):
            return jnp.where(keep_mask[:, None, None], cached, computed)

        _splice_jit = _impl
    return mempool.run_donated(_splice_jit, computed, cached, keep_mask)
