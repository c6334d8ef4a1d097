"""Library lifecycle.

Analog of `dbcsr_init_lib` / `dbcsr_finalize_lib`
(`src/core/dbcsr_lib.F:108-366`).  The reference's per-rank GPU
round-robin device pick, acc_init, and per-thread pool setup collapse
into: enable 64-bit dtypes (this is a double-precision library) and
reset statistics.  Auto-initialization on first use is provided because
there is no Fortran-style hard ordering requirement in Python.
"""

from __future__ import annotations

import os
import time

import jax

from dbcsr_tpu.core import stats
from dbcsr_tpu.core import timings
from dbcsr_tpu.obs import metrics

_initialized = False
_before_init_booked = False

# the persistent compile cache's home when the environment names none:
# a FIXED directory in the checkout (git-ignored) — the path is part of
# the cache key, so one made from tempfile, a pid or the time never hits
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory, once, at
    package import (the one place every process passes).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it: the
    program keeps its cache there and sets no other.  Returns the
    directory in effect.

    Every program is cached, however small or quick to compile: JAX's
    default skips compiles under 1 s, which on a v5e kept 11 of
    chip_smoke's 146 programs and left a warm run 35.7 s of set-up
    where caching all of them leaves 24.0 (PERF.md, PR 21).  Two
    exceptions keep JAX's thresholds: an environment that sets them, and
    a process pinned to the CPU backend, where serializing an executable
    means compiling it a second time — a cold tier-1 run took 842 s
    against 506 with every program cached."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
    if jax.config.jax_platforms != "cpu":
        for option, everything in (
                ("jax_persistent_cache_min_compile_time_secs", 0.0),
                ("jax_persistent_cache_min_entry_size_bytes", -1)):
            if option.upper() not in os.environ:
                jax.config.update(option, everything)
    return jax.config.jax_compilation_cache_dir


# glibc `mallopt` parameters: M_MMAP_THRESHOLD at the largest value a
# 64-bit host takes (HEAP_MAX_SIZE / 2), M_TRIM_THRESHOLD at 1 GiB
_MALLOPT = ((-3, 32 << 20), (-1, 1 << 30))


def steady_host_allocator() -> bool:
    """Fix glibc malloc's mmap threshold at its 32 MiB ceiling and its
    trim threshold at 1 GiB, once, at package import.  Returns whether
    the C library took both (False off glibc: nothing is changed there).

    The index phase of a multiply allocates and frees NumPy arrays of
    several MB each (0.83 M candidate triples at the north star).  Left
    alone, glibc serves an allocation over its threshold (128 KiB, and
    then whatever the largest mmapped chunk freed so far happened to be)
    with a fresh `mmap`: the kernel zero-fills every page again on every
    product.  On a v5e host that is 25 ms of a 225 ms f32 north-star
    product, and WHICH processes pay it is an accident: one that
    compiled any program (the compiler frees a chunk near 32 MiB, which
    lifts the dynamic thresholds for good) ran every product at 0.225 s,
    one that loaded all its executables from the compile cache at
    0.250 s (my chip runs, PR 26: PERF.md section 6).  With the mmap
    threshold fixed, those arrays come from the heap and every process
    is the fast one.  Fixing it switches the dynamic adjustment off and
    leaves the trim threshold at 128 KiB, where the heap's top is handed
    back and faulted in again all the time (mixed blocks at 10 000 went
    0.411 -> 0.447 s a product): so the trim threshold is set too, and
    the process keeps up to 1 GiB of freed heap.  Arrays over 32 MiB are
    mmapped as before."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):  # no dlopen(NULL), or no mallopt
        return False
    mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), ctypes.c_int
    return all([bool(mallopt(param, value)) for param, value in _MALLOPT])


def _seconds_since_process_start() -> float | None:
    """Age of this process by the kernel's account (`/proc/self/stat`
    field 22, clock ticks after boot, to 10 ms); None where there is no
    such file."""
    try:
        with open("/proc/self/stat") as fh:
            # the command name, field 2, may hold spaces and brackets
            fields = fh.read().rpartition(")")[2].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def init_lib(enable_x64: bool = True) -> None:
    """Once a process: 64-bit dtypes, and JAX's compile events booked
    into the registry (`obs.metrics.listen_to_compiles`).  The first
    call also books the region ``before_init`` in the timer table:
    everything from the process's start to here (interpreter,
    ``import jax``, the device client where the caller made one,
    ``import dbcsr_tpu``, whose own span ``import`` lies inside it)."""
    global _initialized, _before_init_booked
    if _initialized:
        return
    if not _before_init_booked:
        _before_init_booked = True
        age = _seconds_since_process_start()
        if age is not None:
            timings.book("before_init", age, children=("import",))
    with timings.timed("init_lib"):
        if enable_x64:
            jax.config.update("jax_enable_x64", True)
        metrics.listen_to_compiles()
    _initialized = True


def ensure_init() -> None:
    if not _initialized:
        init_lib()


def finalize_lib(print_stats: bool = False, out=print) -> None:
    global _initialized
    if print_stats:
        print_statistics(out=out)
    stats.reset()
    timings.reset()
    _initialized = False


def _print_obs_summary(out=print) -> None:
    """Finalize parity for the obs layers: when any of them captured
    something this process (trace session, event bus, introspection
    endpoint — `obs.obs_active`), the end-of-run report also emits ONE
    machine-readable JSON line: the full `metrics.snapshot()` (the
    per-driver roofline rollup, recompile mirror, every counter) plus
    the final `health.verdict()` — DBCSR's finalize-time STATISTICS
    block, extended to cover what the live ops plane was watching.
    Emitted through the same ``out=`` hook as the legacy tables so
    capture harnesses that redirect one redirect both."""
    try:
        from dbcsr_tpu import obs
        from dbcsr_tpu.obs import health as _health
        from dbcsr_tpu.obs import metrics as _metrics

        if not obs.obs_active():
            return
        import json

        out(" -" + "OBS SNAPSHOT (machine-readable)".center(68) + "-")
        out(json.dumps({
            "obs_schema": obs.OBS_SCHEMA_VERSION,
            "snapshot": _metrics.snapshot(),
            "health": _health.verdict(),
        }, default=str))
    except Exception:
        pass  # the legacy report must never fail on the obs extension


def print_statistics(out=print) -> None:
    """Ref `dbcsr_print_statistics` (`src/core/dbcsr_lib.F:326`)."""
    stats.print_statistics(out=out)
    timings.report(out=out)
    _print_obs_summary(out=out)
