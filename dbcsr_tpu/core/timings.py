"""Timer framework.

Analog of the reference timing subsystem (`src/core/dbcsr_timings.F`:
timeset/timestop handlers with a call stack, per-routine self/total
time; report at `dbcsr_timings_report.F:51`; cachegrind callgraph export
at :303).  Host apps can override via `set_hooks`, mirroring
`dbcsr_base_hooks.F:88-110`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

# stdlib-only module; feeds every timed() region to the span tracer
# when one is active (obs.tracer._tracer is None otherwise — a single
# attribute check on the off path)
from dbcsr_tpu.obs import tracer as _trace


@dataclasses.dataclass
class _RoutineStat:
    calls: int = 0
    total: float = 0.0  # inclusive
    self_time: float = 0.0  # exclusive
    callees: dict = dataclasses.field(default_factory=dict)  # name -> (calls, time)


_stats: dict[str, _RoutineStat] = {}
_stack: list[list] = []  # entries: [name, t_start, child_time]
_hooks = None  # optional (timeset_fn, timestop_fn) override


def set_hooks(timeset_fn, timestop_fn) -> None:
    """Install host-application timer hooks (ref `dbcsr_init_lib_hooks`,
    `dbcsr_base_hooks.F:54-110`); ``set_hooks(None, None)`` restores
    the built-in timer."""
    global _hooks
    _hooks = None if timeset_fn is None and timestop_fn is None else (
        timeset_fn, timestop_fn
    )


def timeset(name: str) -> None:
    if _hooks:
        _hooks[0](name)
        return
    _stack.append([name, time.perf_counter(), 0.0])
    if _trace._tracer is not None:
        _trace._tracer.begin(name)


def timestop(name: str) -> None:
    if _hooks:
        _hooks[1](name)
        return
    ent = _stack.pop()
    assert ent[0] == name, f"timer mismatch: stopped {name}, open {ent[0]}"
    dt = time.perf_counter() - ent[1]
    if _trace._tracer is not None:
        _trace._tracer.end(name, dur_s=dt)
    st = _stats.setdefault(name, _RoutineStat())
    st.calls += 1
    st.total += dt
    st.self_time += dt - ent[2]
    if _stack:
        parent = _stack[-1]
        parent[2] += dt
        pst = _stats.setdefault(parent[0], _RoutineStat())
        c, t = pst.callees.get(name, (0, 0.0))
        pst.callees[name] = (c + 1, t + dt)


# resolved once on first use: timed() sits on every phase boundary and
# the per-call import lookup is measurable at driver-loop frequency
_TraceAnnotation = None
_ta_resolved = False


@contextlib.contextmanager
def timed(name: str):
    """Timer + device-profiler range.

    Besides the host timer, each phase is emitted as a
    `jax.profiler.TraceAnnotation` so xprof/perfetto traces show the
    engine phases — the NVTX/ROCTX range analog
    (`src/acc/cuda/dbcsr_cuda_nvtx_cu.cpp`, `dbcsr_cuda_profiling.F`).
    The host-side span goes to `obs.tracer` (via timeset/timestop) with
    the same name, so the Chrome-trace export lines up with device
    profiles.
    """
    global _TraceAnnotation, _ta_resolved
    if not _ta_resolved:
        try:
            from jax.profiler import TraceAnnotation as _ta

            _TraceAnnotation = _ta
        except ImportError:  # pragma: no cover - jax always present
            _TraceAnnotation = None
        _ta_resolved = True
    timeset(name)
    try:
        if _TraceAnnotation is None:
            yield
        else:
            with _TraceAnnotation(f"dbcsr_tpu:{name}"):
                yield
    finally:
        timestop(name)


def book(name: str, seconds: float, children=()) -> None:
    """Book a region that no `timed` block can bracket, measured by the
    caller: ``import`` (the package's own `__init__`, which runs before
    this module exists) and ``before_init`` (from the process's start).
    One call, ``seconds`` of total time; the totals already booked under
    the names in ``children`` lay inside it and are taken off its self
    time.  No open span, no tracer event, no profiler range."""
    st = _stats.setdefault(name, _RoutineStat())
    st.calls += 1
    st.total += seconds
    st.self_time += seconds - sum(
        _stats[c].total for c in children if c in _stats)


@contextlib.contextmanager
def booked(name: str):
    """`book` around a block: for a region that a thread other than the
    engine's may run while spans are open (a client staging blocks beside
    a multiply, the native library's first build).  `timed` keeps one
    stack for the process and asserts its order, so it cannot bracket
    such a block; this one touches no stack, and so is no ``phase`` of a
    compile and takes nothing off an enclosing span's self time."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        book(name, time.perf_counter() - t0)


def device_scope(name: str):
    """Name a phase INSIDE a device program: the device counterpart of
    `timed`.

    `timed` brackets host code: its span is host time on the profiler's
    clock (around an async dispatch that is the dispatch, never the
    device's work).  This returns `jax.named_scope(name)` and nothing
    else: ops created under it while JAX traces a jitted function carry
    the name in their HLO metadata (`op_name`), a device trace hands it
    back with every op event (`tf_op`), and device time can be summed
    per phase.  A scope exists only while the function is traced: no
    timer, no tracer call, nothing per launch.

    The persistent compile cache keys a program WITHOUT its metadata,
    so an executable cached before a scope was added or renamed is
    loaded in the new code's place, scopeless: give the jitted function
    a new name whenever its scopes change (`acc.smm`)."""
    import jax

    return jax.named_scope(name)


def reset() -> None:
    _stats.clear()
    _stack.clear()
    if _trace._tracer is not None:
        # keep the tracer's span stack in sync with the timer stack
        _trace._tracer._span_stack.clear()


def report(out=print, top: int = 30, aggregate: bool = False) -> None:
    """Per-routine table, self-time ordered (ref timings_report.F:51).

    ``aggregate=True`` in a multi-process world prints the
    rank-aggregated table — AVERAGE and MAX self/total time per routine
    across processes, on the coordinator only (ref the MPI-aggregated
    report, `dbcsr_timings_report.F:51-301`)."""
    if aggregate:
        import jax
    if aggregate and jax.process_count() > 1:
        rows = _aggregate_ranks()
        if rows is None or jax.process_index() != 0:
            return
        out(" " + "-" * 88)
        out(" -" + f"T I M I N G  ({jax.process_count()} ranks)".center(86) + "-")
        out(" " + "-" * 88)
        out(f" {'SUBROUTINE':<30} {'CALLS':>8} {'SELF avg':>10} "
            f"{'SELF max':>10} {'TOT avg':>10} {'TOT max':>10}")
        for name, calls, s_avg, s_max, t_avg, t_max in rows[:top]:
            out(f" {name:<30} {calls:>8} {s_avg:>10.3f} {s_max:>10.3f} "
                f"{t_avg:>10.3f} {t_max:>10.3f}")
        out(" " + "-" * 88)
        return
    if not _stats:
        return
    out(" " + "-" * 70)
    out(" -" + "T I M I N G".center(68) + "-")
    out(" " + "-" * 70)
    out(f" {'SUBROUTINE':<36} {'CALLS':>8} {'SELF [s]':>11} {'TOTAL [s]':>11}")
    rows = sorted(_stats.items(), key=lambda kv: -kv[1].self_time)[:top]
    for name, st in rows:
        out(f" {name:<36} {st.calls:>8} {st.self_time:>11.3f} {st.total:>11.3f}")
    out(" " + "-" * 70)


_AGG_MAX_ROUTINES = 64
_AGG_NAME_BYTES = 40


def _aggregate_ranks():
    """Gather every rank's (name, calls, self, total) table via
    `process_allgather` (fixed-shape padded arrays — routine sets may
    differ per rank) and reduce to per-routine avg/max rows sorted by
    avg self time.  Returns None when no rank has timings."""
    import numpy as np
    from jax.experimental import multihost_utils

    local = sorted(_stats.items(), key=lambda kv: -kv[1].self_time)
    local = local[:_AGG_MAX_ROUTINES]
    names = np.zeros((_AGG_MAX_ROUTINES, _AGG_NAME_BYTES), np.uint8)
    vals = np.zeros((_AGG_MAX_ROUTINES, 3), np.float64)
    for i, (name, st) in enumerate(local):
        raw = name.encode()
        if len(raw) > _AGG_NAME_BYTES:
            # keep long names distinct after truncation: last 6 bytes
            # carry a content hash, not the (possibly shared) prefix
            import hashlib

            raw = raw[: _AGG_NAME_BYTES - 6] + hashlib.sha1(raw).hexdigest()[:6].encode()
        names[i, : len(raw)] = np.frombuffer(raw, np.uint8)
        vals[i] = (st.calls, st.self_time, st.total)
    gathered = multihost_utils.process_allgather((names, vals))
    all_names = np.asarray(gathered[0])
    all_vals = np.asarray(gathered[1])
    table = {}
    for r in range(all_names.shape[0]):
        for i in range(_AGG_MAX_ROUTINES):
            raw = bytes(all_names[r, i][all_names[r, i] != 0])
            if not raw:
                continue
            name = raw.decode(errors="replace")
            calls, s, t = all_vals[r, i]
            e = table.setdefault(name, [0, [], []])
            e[0] = max(e[0], int(calls))
            e[1].append(float(s))
            e[2].append(float(t))
    if not table:
        return None
    nproc = all_names.shape[0]
    rows = []
    for name, (calls, selfs, tots) in table.items():
        # ranks missing the routine contribute 0 to the average, like
        # the reference's sum/nranks
        s_avg = sum(selfs) / nproc
        t_avg = sum(tots) / nproc
        rows.append((name, calls, s_avg, max(selfs), t_avg, max(tots)))
    rows.sort(key=lambda r: -r[2])
    return rows


def export_callgraph(path: str) -> None:
    """Cachegrind-format callgraph (ref timings_report.F:303-351)."""
    with open(path, "w") as f:
        f.write("events: Walltime_usec\n\n")
        for name, st in _stats.items():
            f.write(f"fn={name}\n1 {int(st.self_time * 1e6)}\n")
            for callee, (calls, t) in st.callees.items():
                f.write(f"cfn={callee}\ncalls={calls} 1\n1 {int(t * 1e6)}\n")
            f.write("\n")
