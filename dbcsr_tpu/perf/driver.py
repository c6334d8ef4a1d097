"""Performance driver: the `dbcsr_perf` analog.

Replicates `tests/dbcsr_performance_driver.F` +
`dbcsr_performance_multiply.F`: parse a `.perf` input (same format as
`tests/input.perf` in the reference), build random block-sparse
matrices, run nrep multiplies, report per-repeat time and mean/std
GFLOP/s plus checksums.

Grid handling (ref `dbcsr_performance_driver.F:47-56` mp_cart_create):
``npcols > 0`` selects the process-grid columns.  On the device mesh
this maps to a ('kl','pr','pc') mesh with pr = pc = npcols and any
excess device factor becoming 2.5D k-layers (`kl`), the analog of
NUM_LAYERS_3D; ``use_rma=T`` (the reference's one-sided 3D algorithm,
`dbcsr_mm_3d.F:1136`) prefers a layered kl>1 mesh.  npcols == 0 with
one device runs the single-chip engine.

Checksum verification (ref `dbcsr_performance_multiply.F:584-675`):
when the input's ``check`` flag is set, checksum(C_out) and the
position-dependent checksum are compared against the recorded reference
values with the reference's relative-difference formula, and a
`PerfChecksumError` is raised on mismatch.

Usage:  python -m dbcsr_tpu.perf.driver tests/inputs/test_square_sparse.perf [ndevices]
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import List, Optional, Tuple

import numpy as np

import jax

from dbcsr_tpu.core.kinds import dtype_of
from dbcsr_tpu.core.matrix import BlockSparseMatrix
from dbcsr_tpu.ops.test_methods import checksum as matrix_checksum
from dbcsr_tpu.ops.test_methods import make_random_matrix
from dbcsr_tpu.mm.multiply import multiply


@dataclasses.dataclass
class PerfConfig:
    npcols: int = 0
    use_rma: bool = False
    operation: str = "dbcsr_multiply"
    m: int = 1000
    n: int = 1000
    k: int = 1000
    sparsity_a: float = 0.0
    sparsity_b: float = 0.0
    sparsity_c: float = 0.0
    transa: str = "N"
    transb: str = "N"
    symm_a: str = "N"
    symm_b: str = "N"
    symm_c: str = "N"
    data_type: int = 3
    alpha: complex = 1.0
    beta: complex = 1.0
    limits: Tuple[int, int, int, int, int, int] = (0, 0, 0, 0, 0, 0)
    retain_sparsity: bool = False
    nrep: int = 1
    m_sizes: List[Tuple[int, int]] = dataclasses.field(default_factory=lambda: [(1, 5)])
    n_sizes: List[Tuple[int, int]] = dataclasses.field(default_factory=lambda: [(1, 5)])
    k_sizes: List[Tuple[int, int]] = dataclasses.field(default_factory=lambda: [(1, 5)])
    check: bool = False
    check_threshold: float = 0.0
    check_refs: Tuple[float, float] = (0.0, 0.0)


class PerfChecksumError(RuntimeError):
    """checksum(C_out) disagrees with the input file's reference value
    (ref: dbcsr_abort 'Wrong Checksums. Test failed!',
    `dbcsr_performance_multiply.F:673-675`)."""


def _fortran_bool(tok: str) -> bool:
    return tok.strip().upper().startswith("T")


def _fortran_float(tok: str) -> float:
    return float(tok.strip().lower().replace("d", "e"))


def parse_perf_file(path: str) -> PerfConfig:
    """Parse the reference `.perf` format (`tests/input.perf`): positional
    values, '#' comments."""
    with open(path) as f:
        toks = [ln.strip() for ln in f if ln.strip() and not ln.strip().startswith("#")]
    it = iter(toks)
    nx = lambda: next(it)  # noqa: E731
    cfg = PerfConfig()
    cfg.npcols = int(nx())
    cfg.use_rma = _fortran_bool(nx())
    cfg.operation = nx()
    cfg.m, cfg.n, cfg.k = int(nx()), int(nx()), int(nx())
    cfg.sparsity_a = _fortran_float(nx())
    cfg.sparsity_b = _fortran_float(nx())
    cfg.sparsity_c = _fortran_float(nx())
    cfg.transa, cfg.transb = nx(), nx()
    cfg.symm_a, cfg.symm_b, cfg.symm_c = nx(), nx(), nx()
    cfg.data_type = int(nx())
    ar, ai_ = _fortran_float(nx()), _fortran_float(nx())
    br, bi = _fortran_float(nx()), _fortran_float(nx())
    cfg.alpha = complex(ar, ai_) if ai_ else ar
    cfg.beta = complex(br, bi) if bi else br
    cfg.limits = tuple(int(nx()) for _ in range(6))
    cfg.retain_sparsity = _fortran_bool(nx())
    cfg.nrep = int(nx())
    nm, nn, nk = int(nx()), int(nx()), int(nx())
    cfg.m_sizes = [(int(nx()), int(nx())) for _ in range(nm)]
    cfg.n_sizes = [(int(nx()), int(nx())) for _ in range(nn)]
    cfg.k_sizes = [(int(nx()), int(nx())) for _ in range(nk)]
    cfg.check = _fortran_bool(nx())
    cfg.check_threshold = _fortran_float(nx())
    cfg.check_refs = (_fortran_float(nx()), _fortran_float(nx()))
    return cfg


def expand_block_sizes(total: int, pattern: List[Tuple[int, int]]) -> np.ndarray:
    """Cycle (multiplicity, size) pairs until `total` is covered
    (ref `dbcsr_performance_multiply.F` block-size multisets)."""
    sizes = []
    covered = 0
    while covered < total:
        for mult, size in pattern:
            for _ in range(mult):
                take = min(size, total - covered)
                if take <= 0:
                    break
                sizes.append(take)
                covered += take
            if covered >= total:
                break
    return np.asarray(sizes, np.int32)


def _element_limits(lim_lo, lim_hi) -> Tuple[Optional[int], Optional[int]]:
    """1-based .perf limits (0 = open) -> 0-based inclusive element
    limits for `multiply(element_limits=...)` (exact, incl. limits that
    do not align with block boundaries — ref `dbcsr_crop_matrix`).
    Each side defaults independently, like the reference
    (`dbcsr_performance_multiply.F:171-178`)."""
    return (None if lim_lo == 0 else lim_lo - 1,
            None if lim_hi == 0 else lim_hi - 1)


def _mesh_for(cfg: PerfConfig, n_devices: int):
    """Device mesh honoring npcols/use_rma (see module docstring); None
    means run the single-chip engine."""
    if n_devices <= 1 and cfg.npcols <= 1:
        return None
    from dbcsr_tpu.parallel import make_grid

    if cfg.npcols > 0:
        s = cfg.npcols
        if n_devices % (s * s):
            raise ValueError(
                f"npcols={s} needs a device count divisible by {s * s}, "
                f"have {n_devices}"
            )
        kl = n_devices // (s * s)
        if kl == 1 and s == 1:
            return None  # 1x1 grid: single-chip engine
        import jax

        devices = jax.devices()[: kl * s * s]
        if len(devices) < kl * s * s:
            raise ValueError(
                f"grid kl={kl} x {s}x{s} needs {kl * s * s} devices, "
                f"have {len(devices)}"
            )
        from jax.sharding import Mesh

        return Mesh(np.asarray(devices).reshape(kl, s, s),
                    axis_names=("kl", "pr", "pc"))
    return make_grid(n_devices, layers=2 if cfg.use_rma and n_devices >= 8 else None)


def run_perf(cfg: PerfConfig, seed: int = 12341313, verbose: bool = True,
             n_devices: Optional[int] = None, mesh=None):
    """Run the configured multiply nrep times; returns a result dict
    (ref `perf_multiply`, `dbcsr_performance_multiply.F:452-515`).

    ``n_devices`` > 1 (or npcols > 1 in the input) runs on the device
    mesh via the distributed sparse Cannon; default is single-chip.
    ``mesh`` overrides the grid entirely (the multi-process mode passes
    the jax.distributed world mesh).
    """
    dtype = dtype_of(cfg.data_type)
    rng = np.random.default_rng(seed)
    m_sizes = expand_block_sizes(cfg.m, cfg.m_sizes)
    n_sizes = expand_block_sizes(cfg.n, cfg.n_sizes)
    k_sizes = expand_block_sizes(cfg.k, cfg.k_sizes)

    a_rbs, a_cbs = (m_sizes, k_sizes) if cfg.transa == "N" else (k_sizes, m_sizes)
    b_rbs, b_cbs = (k_sizes, n_sizes) if cfg.transb == "N" else (n_sizes, k_sizes)
    a = make_random_matrix("A", a_rbs, a_cbs, dtype=dtype,
                           occupation=1.0 - cfg.sparsity_a,
                           matrix_type=cfg.symm_a, rng=rng)
    b = make_random_matrix("B", b_rbs, b_cbs, dtype=dtype,
                           occupation=1.0 - cfg.sparsity_b,
                           matrix_type=cfg.symm_b, rng=rng)
    c = make_random_matrix("C", m_sizes, n_sizes, dtype=dtype,
                           occupation=1.0 - cfg.sparsity_c,
                           matrix_type=cfg.symm_c, rng=rng)

    el = (*_element_limits(cfg.limits[0], cfg.limits[1]),
          *_element_limits(cfg.limits[2], cfg.limits[3]),
          *_element_limits(cfg.limits[4], cfg.limits[5]))
    has_limits = any(x is not None for x in el)

    if n_devices is None:
        n_devices = int(os.environ.get("DBCSR_TPU_PERF_DEVICES", "1"))
    if mesh is None:
        mesh = _mesh_for(cfg, n_devices)

    chksum_a = matrix_checksum(a)
    chksum_b = matrix_checksum(b)
    chksum_c_in = matrix_checksum(c)

    from dbcsr_tpu.core import stats as _stats

    def _rollup_bytes():
        return sum(v["bytes"] for v in _stats.driver_rollup().values())

    bytes0 = _rollup_bytes()

    def _run_once():
        """One timed repeat of the configured multiply — also the body
        the checksum gate's one-shot safe-driver retry re-executes.
        Returns (c_run, flops, elapsed_s); timing excludes the C copy
        and its completion fence (the reference's contract)."""
        c_run = c.copy()
        _force_completion(c_run)
        t0 = time.perf_counter()
        if mesh is not None:
            from dbcsr_tpu.parallel.sparse_dist import sparse_multiply_distributed

            if (cfg.transa, cfg.transb) != ("N", "N") or cfg.symm_a != "N" \
                    or cfg.symm_b != "N" or cfg.symm_c != "N":
                from dbcsr_tpu.ops.transformations import desymmetrize, new_transposed
                from dbcsr_tpu.core.kinds import is_complex as _is_cplx
                from dbcsr_tpu.core.matrix import NO_SYMMETRY

                def _op(mat, tr):
                    m_ = desymmetrize(mat) if mat.matrix_type != NO_SYMMETRY else mat
                    if tr == "T":
                        return new_transposed(m_)
                    if tr == "C":
                        return new_transposed(m_, conjugate=_is_cplx(m_.dtype))
                    return m_

                a_eff, b_eff = _op(a, cfg.transa), _op(b, cfg.transb)
            else:
                a_eff, b_eff = a, b
            c_run = sparse_multiply_distributed(
                cfg.alpha, a_eff, b_eff, cfg.beta, c_run, mesh,
                retain_sparsity=cfg.retain_sparsity,
                element_limits=el if has_limits else None,
            )
            flops = int(getattr(c_run, "_last_flops", 0))
        else:
            flops = multiply(
                cfg.transa, cfg.transb, cfg.alpha, a, b, cfg.beta, c_run,
                retain_sparsity=cfg.retain_sparsity,
                element_limits=el if has_limits else None,
            )
        _force_completion(c_run)
        return c_run, flops, time.perf_counter() - t0

    times, flops_list = [], []
    # repeated-identical reps must measure the ENGINE: with the
    # delta-aware incremental plane live, rep 3+ of an unchanged
    # beta==0 product would legitimately serve the cached result with
    # zero launches, turning gflops into a cache benchmark
    from dbcsr_tpu.core.config import get_config as _get_cfg
    from dbcsr_tpu.core.config import set_config as _set_cfg

    _prev_inc = _get_cfg().incremental
    _set_cfg(incremental="off")
    try:
        for _ in range(cfg.nrep):
            c_run, flops, dt = _run_once()
            times.append(dt)
            flops_list.append(flops)
    finally:
        _set_cfg(incremental=_prev_inc)
    gflops = [f / t / 1e9 for f, t in zip(flops_list, times)]
    cs = matrix_checksum(c_run)
    cs_pos = matrix_checksum(c_run, pos=True)
    result = {
        "times_s": times,
        "flops": flops_list[-1],
        "gflops_mean": float(np.mean(gflops)),
        "gflops_std": float(np.std(gflops)),
        "gflops_best": float(np.max(gflops)),
        "checksum": cs,
        "checksum_pos": cs_pos,
        "checksum_a": chksum_a,
        "checksum_b": chksum_b,
        "checksum_c_in": chksum_c_in,
        "device": str(jax.devices()[0]),
        "grid": dict(mesh.shape) if mesh is not None else {"pr": 1, "pc": 1},
        # which algorithm the engine chose ("dense" = cost-model dense
        # mode; GFLOP/s above is always TRUE sparse-product flops / time)
        "algorithm": getattr(c_run, "_mm_algorithm", "mesh"),
    }
    # cost-model-normalized attribution of the best repeat: modeled HBM
    # bytes per multiply (delta of the per-driver rollup over the rep
    # loop), achieved GFLOP/s on TRUE flops, and the roofline fraction
    # against this device_kind's peak table (obs/costmodel.py) — the
    # efficiency numbers bench.py embeds for tools/perf_gate.py
    from dbcsr_tpu.obs import costmodel as _costmodel

    bytes_per_rep = (_rollup_bytes() - bytes0) / max(cfg.nrep, 1)
    result["modeled"] = _costmodel.roofline(
        flops_list[-1], bytes_per_rep, min(times),
        dtype=np.dtype(dtype).name,
    )
    from dbcsr_tpu.obs import tracer as _obs_tracer

    if _obs_tracer.active():
        # a traced perf run leaves its JSONL *and* the Chrome trace on
        # disk even if the process lives on (bench loops, pytest)
        _obs_tracer.get().flush()
    if verbose:
        print(f" matrix sizes M/N/K          {cfg.m} {cfg.n} {cfg.k}")
        print(f" sparsities A/B/C            {cfg.sparsity_a} {cfg.sparsity_b} {cfg.sparsity_c}")
        print(f" device                      {result['device']}")
        print(f" grid (kl x pr x pc)         {result['grid']}")
        print(f" flops per multiply          {result['flops']:,}")
        print(f" time per multiply           {[f'{t:.4f}' for t in times]}")
        print(f" perf total                  {result['gflops_mean']:.2f} +/- "
              f"{result['gflops_std']:.2f} GFLOP/s (best {result['gflops_best']:.2f})")
        print(f" checksum(A)                 {chksum_a:.15e}")
        print(f" checksum(B)                 {chksum_b:.15e}")
        print(f" checksum(C_in)              {chksum_c_in:.15e}")
        print(f" checksum(C_out)             {cs:.15e}")
        print(f" checksum(C_out) POS         {cs_pos:.15e}")
    if cfg.check:
        try:
            _verify_checksums(cfg, cs, cs_pos, verbose)
        except PerfChecksumError as first_err:
            # black-box dump: what was the engine doing for the last N
            # multiplies when the checksum tripped (obs flight recorder)
            from dbcsr_tpu.obs import flight

            flight.dump()
            # one-shot safe-driver retry: re-run ONE repeat on the
            # plain XLA stack path (no pallas, no dense mode) and
            # classify the failure as deterministic vs transient vs
            # driver-specific (see _checksum_retry_safe)
            result = _checksum_retry_safe(cfg, _run_once, cs, first_err,
                                          result, verbose)
    return result


def _verify_checksums(cfg: PerfConfig, cs: float, cs_pos: float, verbose: bool) -> None:
    """The reference's relative-difference acceptance
    (`dbcsr_performance_multiply.F:656-675`)."""
    th = cfg.check_threshold
    errs = []
    for name, got, ref in (("checksum(C_out)", cs, cfg.check_refs[0]),
                           ("checksum(C_out) POS", cs_pos, cfg.check_refs[1])):
        # sign-safe version of the reference's ABS(got/MAX(ref, th) - 1):
        # the POS checksum can legitimately be negative here (normal-
        # distributed data), which the reference formula cannot handle
        rel_diff = abs(got - ref) / max(abs(ref), th)
        if rel_diff > th:
            errs.append(f"Wrong {name}: got {got:.15e}, ref {ref:.15e}, "
                        f"rel_diff {rel_diff:.3e} > threshold {th:.1e}")
    if errs:
        raise PerfChecksumError("; ".join(errs))
    if verbose:
        print(" checksums OK (within threshold)")


# the chain driver every backend can run and every test trusts: the
# plain XLA stack path (dense mode disabled for the retry too — the
# corruption may live in the dense carve)
SAFE_DRIVER = "xla"


def _checksum_retry_safe(cfg: PerfConfig, run_once, cs_first: float,
                         first_err: PerfChecksumError, result: dict,
                         verbose: bool) -> dict:
    """One-shot safe-driver retry for a tripped checksum gate.

    Re-runs ONE repeat with ``mm_driver=SAFE_DRIVER`` (and dense mode
    off) and classifies the original failure:

    * retry passes, original config used a different driver path →
      ``driver`` — the selected driver deterministically corrupts this
      workload (the breaker layer has already quarantined it per
      shape); the safe result is returned.
    * retry passes, original config was already the safe driver →
      ``transient`` — same path, different outcome; the safe result is
      returned.
    * retry reproduces the SAME wrong checksum → ``deterministic`` —
      engine-level (or reference-value) error; re-raised.
    * retry fails with a different checksum → ``unstable`` — re-raised.

    The classification lands in the
    ``dbcsr_tpu_checksum_retry_total{outcome}`` counter, the returned
    result dict (``checksum_retry``), and the raised message."""
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import events as _events
    from dbcsr_tpu.obs import metrics as _metrics

    def _publish_retry(outcome: str) -> None:
        # the bus record correlates the retry verdict with the flight
        # records already dumped (same process, adjacent products)
        _events.publish("checksum_retry", {
            "outcome": outcome, "safe_driver": SAFE_DRIVER,
            "original_mm_driver": prev_driver,
            "error": str(first_err)[:300],
        })

    live = get_config()
    prev_driver, prev_format = live.mm_driver, live.mm_format
    retried_same_path = prev_driver == SAFE_DRIVER
    try:
        set_config(mm_driver=SAFE_DRIVER, mm_format="stack")
        c_run, _flops, _dt = run_once()
    except Exception as exc:  # retry itself died: original error stands
        _metrics.counter(
            "dbcsr_tpu_checksum_retry_total",
            "checksum-gate safe-driver retries by outcome",
        ).inc(outcome="retry_error")
        _publish_retry("retry_error")
        raise PerfChecksumError(
            f"{first_err}; safe-driver retry also failed "
            f"({type(exc).__name__}: {exc})") from first_err
    finally:
        set_config(mm_driver=prev_driver, mm_format=prev_format)
    cs = matrix_checksum(c_run)
    cs_pos = matrix_checksum(c_run, pos=True)
    counter = _metrics.counter(
        "dbcsr_tpu_checksum_retry_total",
        "checksum-gate safe-driver retries by outcome",
    )
    try:
        _verify_checksums(cfg, cs, cs_pos, verbose=False)
    except PerfChecksumError:
        outcome = ("deterministic" if cs == cs_first else "unstable")
        counter.inc(outcome=outcome)
        _publish_retry(outcome)
        raise PerfChecksumError(
            f"{first_err}; safe-driver ({SAFE_DRIVER}) retry "
            f"{'reproduced the same wrong checksum' if cs == cs_first else f'produced yet another checksum {cs:.15e}'}"
            f" — classified {outcome.upper()}") from first_err
    outcome = "transient" if retried_same_path else "driver"
    counter.inc(outcome=outcome)
    _publish_retry(outcome)
    if verbose:
        print(f" checksum gate: safe-driver retry PASSED — original "
              f"failure classified {outcome.upper()} "
              f"(driver path {prev_driver!r} -> {SAFE_DRIVER!r})")
    result = dict(
        result,
        checksum=cs, checksum_pos=cs_pos,
        checksum_retry={
            "outcome": outcome,
            "failed_checksum": cs_first,
            "safe_driver": SAFE_DRIVER,
            "original_mm_driver": prev_driver,
            "error": str(first_err),
        },
    )
    return result


def _force_completion(matrix: BlockSparseMatrix) -> float:
    """Force completion of the device work producing a matrix: one
    `fetch_fence` (8-byte d2h with a data dependency on the producing
    program) per bin — the timing contract the reference gets from
    mp_sync (`dbcsr_performance_multiply.F:597`)."""
    from dbcsr_tpu.utils.sync import fetch_fence

    total = 0.0
    for b in matrix.bins:
        if b.count:
            total += fetch_fence(b.data)
    return total


def _mp_worker(cfg_path: str, port: int, nproc: int, pid: int,
               ndev: int, nrep: int) -> int:
    """One rank of the multi-process driver world (internal; spawned by
    `run_perf_multiproc`).  Joins the `jax.distributed` world, builds
    the multihost ('kl','pr','pc') mesh, runs the config over it, and
    emits an MPRESULT line for the parent to aggregate — each rank of
    the reference driver is an MPI process doing exactly this
    (`dbcsr_performance_driver.F:47-56`)."""
    import json

    jax.config.update(
        "jax_platforms", os.environ.get("DBCSR_TPU_MP_PLATFORM", "cpu")
    )
    from dbcsr_tpu.parallel import multihost

    ok = multihost.init_multihost(f"localhost:{port}", nproc, pid)
    if not ok:
        print("MPERROR world join failed")
        return 1
    mesh = multihost.make_multihost_grid()
    cfg = parse_perf_file(cfg_path)
    if nrep:
        cfg.nrep = nrep
    try:
        res = run_perf(cfg, verbose=(pid == 0), mesh=mesh)
    except PerfChecksumError as exc:
        print(f"MPERROR {exc}")
        return 1
    print("MPRESULT " + json.dumps({
        "pid": pid, "checksum": res["checksum"],
        "checksum_pos": res["checksum_pos"],
        "flops": res["flops"], "gflops_mean": res["gflops_mean"],
        "time_best_s": min(res["times_s"]),
    }))
    multihost.shutdown_multihost()
    return 0


def aggregate_rank_results(results: list) -> dict:
    """World aggregation of per-rank MPRESULT records: verify the
    cross-rank checksum contract and report the CONSERVATIVE world rate
    — the slowest rank's best repeat sets the time, exactly as the
    straggler sets an MPI world's wall clock
    (ref per-rank reporting, `dbcsr_performance_multiply.F:452-515`)."""
    checksums = {r["checksum"] for r in results}
    if len(checksums) != 1:
        raise RuntimeError(f"rank checksums differ: {sorted(checksums)}")
    flops = results[0]["flops"]
    t_max = max(r["time_best_s"] for r in results)
    return {
        "nproc": len(results),
        "checksum": results[0]["checksum"],
        "flops": flops,
        # conservative world rate: slowest rank's best repeat
        "gflops_world": flops / t_max / 1e9 if t_max > 0 else 0.0,
        "gflops_mean_ranks": float(
            np.mean([r["gflops_mean"] for r in results])
        ),
        "per_rank": results,
    }


def run_perf_multiproc(cfg_path: str, nproc: int, devices_per_proc: int = 4,
                       nrep: Optional[int] = None, timeout: float = 600,
                       verbose: bool = True) -> dict:
    """Spawn an ``nproc``-process `jax.distributed` world running the
    config over the combined multihost mesh (the mpiexec-driven
    reference driver, `dbcsr_performance_driver.F:47-56`).  Returns the
    rank-aggregated result and verifies every rank computed the
    identical checksum (cross-rank determinism, the `dbcsr_checksum`
    contract)."""
    import json
    import socket
    import subprocess

    def _spawn(deadline_s=timeout):
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        env = dict(
            os.environ,
            XLA_FLAGS=(
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={devices_per_proc}"
            ).strip(),
        )
        env.pop("JAX_PLATFORMS", None)  # the worker sets the platform
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "dbcsr_tpu.perf.driver", cfg_path,
                 "--worker", str(port), str(nproc), str(i),
                 str(devices_per_proc), str(nrep or 0)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env=env,
            )
            for i in range(nproc)
        ]
        outs = []
        try:
            for p in procs:
                outs.append(p.communicate(timeout=deadline_s)[0])
        except subprocess.TimeoutExpired:
            outs = None  # port race / hung join: retry with a new port
        finally:
            for p in procs:
                p.kill()
            for p in procs:
                try:
                    p.communicate(timeout=10)
                except Exception:
                    pass
        return procs, outs

    # the multihost join rides the watchdog executor: a hung world is a
    # WEDGED outcome (backoff + fresh port before the one retry), a
    # rank crash is TRANSIENT, and both land in the
    # dbcsr_tpu_watchdog_outcomes_total{name="mp_world_join"} counter
    from dbcsr_tpu.resilience import watchdog as _watchdog

    wd = _watchdog.Watchdog("mp_world_join", deadline_s=timeout,
                            backoff_base_s=1.0, backoff_max_s=15.0)

    def _attempt(deadline_s):
        procs, outs = _spawn(deadline_s)
        if outs is None:
            raise _watchdog.DeadlineExceeded(
                f"{nproc}-process world join overran {deadline_s:.0f}s")
        return procs, outs

    res = wd.run(_attempt, retries=1, retry_on=(_watchdog.WEDGED,))
    if not res.ok:
        raise RuntimeError(
            f"{nproc}-process world never formed (twice): "
            f"outcome={res.outcome} {res.error}")
    procs, outs = res.value
    results = []
    for i, (p, o) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(f"rank {i} failed:\n{o[-3000:]}")
        for line in o.splitlines():
            if line.startswith("MPRESULT "):
                results.append(json.loads(line[len("MPRESULT "):]))
    if len(results) != nproc:
        raise RuntimeError(f"got {len(results)}/{nproc} rank results:\n"
                           + "\n".join(o[-800:] for o in outs))
    agg = aggregate_rank_results(results)
    if verbose:
        print(f" {nproc}-process world: {agg['gflops_world']:.3f} GFLOP/s "
              f"(slowest-rank best), checksum {agg['checksum']:.9e} "
              f"identical on all ranks")
    return agg


def main(argv=None):
    argv = list(argv if argv is not None else sys.argv[1:])
    if not argv:
        print(__doc__)
        return 1
    if "--worker" in argv:
        i = argv.index("--worker")
        cfg_path = argv[0]
        port, nproc, pid, ndev, nrep = (int(x) for x in argv[i + 1: i + 6])
        return _mp_worker(cfg_path, port, nproc, pid, ndev, nrep)
    nproc = None
    if "--nproc" in argv:
        i = argv.index("--nproc")
        nproc = int(argv[i + 1])
        del argv[i: i + 2]
    cfg = parse_perf_file(argv[0])
    n_devices = int(argv[1]) if len(argv) > 1 else None
    try:
        if nproc and nproc > 1:
            run_perf_multiproc(argv[0], nproc)
        else:
            run_perf(cfg, n_devices=n_devices)
    except PerfChecksumError as exc:
        print(f" {exc}")
        print(" Wrong Checksums. Test failed!")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
