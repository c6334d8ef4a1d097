"""Multiplication statistics registry.

Analog of the reference STATISTICS block: per-(m,n,k) flop counters with
driver breakdown, stack counts and sizes (`src/mm/dbcsr_mm_sched.F:390-546`
stats_add/collect/print), marketing-vs-true flops (`dbcsr_mm.F:664-667`).
"""

from __future__ import annotations

import collections
import dataclasses

# stdlib-only module; record_* feed the span tracer when one is active
# (one attribute check on the off path — see obs/tracer.py)
from dbcsr_tpu.obs import tracer as _trace


@dataclasses.dataclass
class _MnkStat:
    nstacks: int = 0
    nentries: int = 0
    flops: int = 0
    by_driver: dict = dataclasses.field(default_factory=dict)
    # flops keyed (driver, dtype) — the full (driver, shape-bucket,
    # dtype) evidence cell the telemetry time-series store samples
    # (obs/timeseries.py); callers without a dtype land under ""
    by_driver_dtype: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _CommStat:
    nmessages: int = 0
    nbytes: int = 0


@dataclasses.dataclass
class _DriverAgg:
    """Per-driver attribution rollup: flops + modeled HBM bytes
    (`obs.costmodel` convention) + host-side dispatch seconds, with a
    per-dtype flop split so the roofline denominator can use the
    dominant dtype's peak.  ``sync_stacks`` counts the regions whose
    seconds were recorded through block_until_ready (DBCSR_TPU_SYNC_
    TIMING at record time) — a rollup row is labeled synchronized only
    when EVERY region was."""
    flops: int = 0
    nbytes: int = 0
    seconds: float = 0.0
    stacks: int = 0
    sync_stacks: int = 0
    by_dtype: dict = dataclasses.field(default_factory=dict)
    # xla_group plans: slots that hold an entry, slots the live chunks
    # launch, and the groups of each width class
    slots_live: int = 0
    slots_launched: int = 0
    groups_by_width: dict = dataclasses.field(default_factory=dict)
    # grouped spans launched, by the form of their dot
    # (`acc.smm.group_dot_form`)
    dot_forms: dict = dataclasses.field(default_factory=dict)
    # launched spans by block shape, "mxnxk" -> [true entries, slots
    # launched with their padding] (`acc.smm._note_launched_entries`)
    entries_by_mnk: dict = dataclasses.field(default_factory=dict)
    # the mesh collect (`parallel.sparse_dist._collect_bins`): blocks of
    # C, and piece slots all-gathered over the grid (pads included)
    collect_live: int = 0
    collect_shipped: int = 0


_by_mnk: dict = collections.defaultdict(_MnkStat)
_comm: dict = collections.defaultdict(_CommStat)
_driver_agg: dict = collections.defaultdict(_DriverAgg)
_totals = {"multiplies": 0, "flops": 0, "marketing_flops": 0}


def _agg_driver(driver: str, flops: int, nbytes: int, seconds: float,
                dtype: str, stacks: int, sync: bool = False) -> None:
    """The one place the per-driver rollup is updated (callers have
    already passed the keep_stats gate)."""
    agg = _driver_agg[driver]
    agg.flops += flops
    agg.nbytes += nbytes
    agg.seconds += seconds
    agg.stacks += stacks
    if sync:
        agg.sync_stacks += stacks
    if dtype:
        agg.by_dtype[dtype] = agg.by_dtype.get(dtype, 0) + flops


def sync_timing_enabled() -> bool:
    """Opt-in synchronized stack timing (``DBCSR_TPU_SYNC_TIMING=1``):
    the multiply engine times each stack/superstack launch through
    ``jax.block_until_ready`` instead of recording dispatch-side
    seconds, so per-driver achieved GFLOP/s in the roofline rollup
    reflects device completion rather than async dispatch.  Each
    record carries its own flag value (``_DriverAgg.sync_stacks``);
    a rollup row reads ``sync=true`` only when EVERY recorded region
    was synchronized, so mid-process flips never mislabel mixed
    aggregates.  Read from the environment per call (once per
    multiply) so tests and in-process A/Bs can flip it."""
    import os

    return os.environ.get("DBCSR_TPU_SYNC_TIMING") == "1"


def record_driver(driver: str, flops: int, *, nbytes: int = 0,
                  seconds: float = 0.0, dtype: str = "",
                  stacks: int = 1, sync: bool = False) -> None:
    """Attribute one executed region (a stack launch, a dense matmul,
    a mesh plan execution) to its driver: flops, modeled bytes moved,
    and host-observed seconds.  Seconds are DISPATCH-side wall time
    unless the caller timed through block_until_ready and says so with
    ``sync=True`` — on async backends the device may still be
    draining, so per-driver achieved GFLOP/s is an attribution signal,
    not a benchmark; the forced-fetch bench numbers remain the ground
    truth."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    _agg_driver(driver, flops, nbytes, seconds, dtype, stacks, sync=sync)


def record_group_tiles(widths, groups, slots_live: int,
                       slots_launched: int, driver: str = "xla_group") -> None:
    """One planned `xla_group` span (or, ``driver`` "mesh", one mesh
    plan's grouped stacks): its width classes with the groups of each,
    and the slots it fills of those it launches."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    agg = _driver_agg[driver]
    agg.slots_live += slots_live
    agg.slots_launched += slots_launched
    for w, n in zip(widths, groups):
        agg.groups_by_width[w] = agg.groups_by_width.get(w, 0) + n


def record_group_dot(dot_form: str, driver: str = "xla_group") -> None:
    """One launched grouped span (``driver`` "mesh": one product's
    grouped mesh stacks) by the form of its dot."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    forms = _driver_agg[driver].dot_forms
    forms[dot_form] = forms.get(dot_form, 0) + 1


def record_launched_entries(driver: str, mnk: str, live: int,
                            launched: int) -> None:
    """One launched span of ``driver`` at block shape ``mnk``
    ("5x13x23"): its true entries and the slots launched for them."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    cell = _driver_agg[driver].entries_by_mnk.setdefault(mnk, [0, 0])
    cell[0] += live
    cell[1] += launched


def record_collect_slots(live: int, shipped: int) -> None:
    """One mesh product's collect: the blocks of C it carved and the
    piece slots it all-gathered for them (bucket pads included)."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    agg = _driver_agg["mesh"]
    agg.collect_live += live
    agg.collect_shipped += shipped


def driver_rollup() -> dict:
    """Plain-dict view of the per-driver attribution aggregates."""
    out = {}
    for d, a in _driver_agg.items():
        out[d] = {
            "flops": a.flops,
            "bytes": a.nbytes,
            "seconds": a.seconds,
            "stacks": a.stacks,
            "sync_stacks": a.sync_stacks,
            "by_dtype": dict(a.by_dtype),
        }
        if a.slots_launched:
            out[d].update(slots_live=a.slots_live,
                          slots_launched=a.slots_launched,
                          groups_by_width=dict(a.groups_by_width))
        if a.dot_forms:
            out[d]["dot_forms"] = dict(a.dot_forms)
        if a.entries_by_mnk:
            out[d]["entries_by_mnk"] = {
                mnk: {"live": live, "launched": launched}
                for mnk, (live, launched) in a.entries_by_mnk.items()}
        if a.collect_shipped:
            out[d].update(collect_live=a.collect_live,
                          collect_shipped=a.collect_shipped)
    return out


def record_stack(m: int, n: int, k: int, nentries: int, *,
                 driver: str, seconds: float | None = None,
                 nbytes: int | None = None, dtype: str = "",
                 sync: bool = False) -> None:
    """Per-(m,n,k) stack accounting with a DRIVER breakdown — the
    reference's BLAS/SMM/ACC split (`dbcsr_mm_sched.F:390-546`) maps to
    {xla, xla_flat, xla_group, pallas, dense, mesh} here.  ``seconds``
    / ``nbytes`` / ``dtype`` additionally feed the per-driver roofline
    rollup (`record_driver`); callers without a cost model pass none
    and still appear in the flop breakdown.  ``sync`` marks seconds
    timed through block_until_ready (see `sync_timing_enabled`)."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    flops = 2 * m * n * k * nentries
    st = _by_mnk[(m, n, k)]
    st.nstacks += 1
    st.nentries += nentries
    st.flops += flops
    st.by_driver[driver] = st.by_driver.get(driver, 0) + flops
    cell = (driver, dtype)
    st.by_driver_dtype[cell] = st.by_driver_dtype.get(cell, 0) + flops
    _agg_driver(driver, flops, nbytes or 0, seconds or 0.0, dtype, 1,
                sync=sync)
    t = _trace._tracer
    if t is not None:
        t.instant("stack", {"mnk": f"{m}x{n}x{k}", "entries": nentries,
                            "driver": driver})
        t.add("stack_entries", nentries)


def record_comm(kind: str, nmessages: int, nbytes: int) -> None:
    """Collective-traffic counters (analog of the reference's MPI
    statistics: message counts/sizes per class,
    `dbcsr_mm_common.F:135` count_mpi_statistics /
    `dbcsr_mpi_statistics_type`).  ``kind`` names the collective
    ('ppermute', 'psum', 'alltoall', 'host2dev', ...)."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    st = _comm[kind]
    st.nmessages += int(nmessages)
    st.nbytes += int(nbytes)
    t = _trace._tracer
    if t is not None:
        t.instant(f"comm:{kind}", {"messages": int(nmessages),
                                   "bytes": int(nbytes)})
        t.add("comm_bytes", int(nbytes))


def record_multiply(marketing_flops: int) -> None:
    _totals["multiplies"] += 1
    _totals["marketing_flops"] += marketing_flops


# Cannon tick-loop overlap attribution, per (engine, grid): the MODELED
# comm/compute ratio (obs.costmodel.cannon_tick_model /
# mesh_tick_model) next to the MEASURED comm-exposed fraction the
# per-tick driver times under DBCSR_TPU_SYNC_TIMING
# (parallel/overlap.py).  metrics.snapshot()["roofline"] folds this
# into the owning driver's rollup row.
_cannon_overlap: dict = {}


def record_cannon_overlap(engine: str, grid: str, *, mode: str | None = None,
                          modeled: float | None = None,
                          measured: float | None = None,
                          shift_exposed_s: float | None = None,
                          compute_s: float | None = None,
                          drop_measured: bool = False) -> None:
    """Merge one multiply's overlap attribution (modeled ratio and/or
    measured exposed fraction) for an (engine, grid) cell; latest
    values win — this is a point-in-time gauge, not an accumulator.
    ``drop_measured`` clears any earlier measured sample from the cell
    (the degrade path: a serial-delivered product must not keep a
    previous double-buffer run's numbers attached to its mode)."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    row = _cannon_overlap.setdefault((engine, grid), {})
    if drop_measured:
        for k in ("measured_exposed", "shift_exposed_s", "compute_s"):
            row.pop(k, None)
    if mode is not None:
        row["mode"] = mode
    if modeled is not None:
        row["modeled_ratio"] = float(modeled)
    if measured is not None:
        row["measured_exposed"] = float(measured)
    if shift_exposed_s is not None:
        row["shift_exposed_s"] = float(shift_exposed_s)
    if compute_s is not None:
        row["compute_s"] = float(compute_s)


def cannon_overlap_rollup() -> dict:
    """{engine: {grid: {mode, modeled_ratio, measured_exposed, ...}}}
    since the last `reset()`."""
    out: dict = {}
    for (engine, grid), row in _cannon_overlap.items():
        out.setdefault(engine, {})[grid] = dict(row)
    return out


# memory high-water meter (analog of `m_memory`, `dbcsr_machine.F`, and
# the `max_memory` line `dbcsr_lib.F:326` prints): host side reads the
# OS-tracked process peak (VmHWM) and current RSS; device side polls the
# PJRT client's allocator stats where the backend provides them (TPU
# does; the CPU backend usually returns nothing).
_memory = {"host_peak": 0, "host_current": 0, "device_peak": 0,
           "device_in_use": 0}
# VmHWM at the last reset(): the OS meter is process-lifetime monotone,
# so "host peak since reset" is VmHWM only when it has grown past this
# baseline; otherwise the best observable bound is max(RSS samples).
_hwm_at_reset = 0


def _read_proc_status(*fields: str):
    """Read byte values for the given `/proc/self/status` prefixes (kB
    fields); returns a tuple in `fields` order, or None on any failure."""
    vals = {f: 0 for f in fields}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                for field in fields:
                    if line.startswith(field):
                        vals[field] = int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return tuple(vals[f] for f in fields)


def sample_memory() -> None:
    """Update the high-water meters; called at the end of every multiply
    (cheap: one /proc read + one local allocator-stats call)."""
    from dbcsr_tpu.core.config import get_config

    if not get_config().keep_stats:
        return
    meters = _read_proc_status("VmHWM:", "VmRSS:")
    if meters is not None:
        hwm, rss = meters
        _memory["host_current"] = rss
        if hwm > _hwm_at_reset:
            _memory["host_peak"] = hwm
        else:  # peak predates the reset; bound by RSS seen since
            _memory["host_peak"] = max(_memory["host_peak"], rss)
    try:
        import jax

        ms = jax.devices()[0].memory_stats()
        if ms:
            in_use = int(ms.get("bytes_in_use", 0))
            _memory["device_in_use"] = in_use
            _memory["device_peak"] = max(
                _memory["device_peak"],
                int(ms.get("peak_bytes_in_use", in_use)),
            )
    except Exception:  # backend without allocator stats / remote hiccup
        pass


def memory_high_water() -> dict:
    """Current meter values (bytes); see `sample_memory`."""
    return dict(_memory)


def total_flops() -> int:
    return sum(s.flops for s in _by_mnk.values())


def reset() -> None:
    global _hwm_at_reset
    _by_mnk.clear()
    _comm.clear()
    _driver_agg.clear()
    _cannon_overlap.clear()
    for k in _totals:
        _totals[k] = 0
    for k in _memory:
        _memory[k] = 0
    # record the monotone OS high-water mark so later samples report the
    # peak SINCE this reset, not the process-lifetime peak (ADVICE r3)
    meters = _read_proc_status("VmHWM:")
    _hwm_at_reset = meters[0] if meters is not None else 0


def print_statistics(out=print) -> None:
    """Format mirrors the reference's DBCSR STATISTICS table
    (documented in `docs/guide/3-developer-guide/4-performance/1-insights.md`)."""
    out(" " + "-" * 70)
    out(" -" + "DBCSR-TPU STATISTICS".center(68) + "-")
    out(" " + "-" * 70)
    out(f" {'COUNT':>24} {'m x n x k':>14} {'entries':>12} {'GFLOP':>12}"
        f"  {'drivers'}")
    tot = 0
    for (m, n, k), st in sorted(_by_mnk.items()):
        tot += st.flops
        drv = ",".join(f"{d}={f / 1e9:.2f}" for d, f in sorted(st.by_driver.items()))
        out(
            f" {st.nstacks:>24} {f'{m}x{n}x{k}':>14} {st.nentries:>12}"
            f" {st.flops / 1e9:>12.3f}  {drv}"
        )
    out(f" {'total (TPU stacks)':>24} {'':>14} {'':>12} {tot / 1e9:>12.3f}")
    out(f" multiplications:       {_totals['multiplies']}")
    out(f" marketing flops:       {_totals['marketing_flops'] / 1e9:.3f} GFLOP")
    if _comm:
        out(" -" + "COLLECTIVE TRAFFIC".center(68) + "-")
        out(f" {'collective':>24} {'messages':>14} {'MB':>12}")
        for kind, st in sorted(_comm.items()):
            out(f" {kind:>24} {st.nmessages:>14} {st.nbytes / 1e6:>12.2f}")
    if _memory["host_peak"]:
        # ref the `max_memory` line of the lib print (`dbcsr_lib.F:326`)
        out(" -" + "MEMORY USAGE".center(68) + "-")
        out(f" {'host peak (VmHWM)':>24} {_memory['host_peak'] / 1e6:>14.1f} MB")
        out(f" {'host current (VmRSS)':>24} {_memory['host_current'] / 1e6:>14.1f} MB")
        if _memory["device_peak"]:
            out(f" {'device peak':>24} {_memory['device_peak'] / 1e6:>14.1f} MB")
            out(f" {'device in use':>24} {_memory['device_in_use'] / 1e6:>14.1f} MB")
    out(" " + "-" * 70)
