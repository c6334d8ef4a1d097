"""Global configuration.

Analog of the reference `dbcsr_cfg` singleton of typed CONF_PAR entries
(`src/core/dbcsr_config.F:142-172`), with env-var overrides
(``DBCSR_TPU_<NAME>``) and programmatic `set_config` like
`dbcsr_set_config` (`src/dbcsr_api.F:174`).

Knobs that only make sense for CUDA streams/OpenMP threads are replaced
by their TPU-native equivalents (stack-size bucketing for jit-cache
reuse, pallas kernel toggles, mesh defaults).
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass
class Config:
    # --- multiply driver selection (ref MM_DRIVER {auto,matmul,blas,smm,xsmm},
    #     dbcsr_config.F:34-38) -> here {auto, xla, xla_group, pallas,
    #     pallas_cross, host} ("host" = native C++ stack driver on
    #     CPU backends, the ref smm/blas CPU path)
    mm_driver: str = "auto"
    # max entries pushed to the device per kernel call before flushing
    # (ref MM_STACK_SIZE: 30000 accel / 1000 CPU, dbcsr_config.F:77-79)
    mm_stack_size: int = 30000
    # ---- storage-format planner (mm/format_planner.py; env
    #      DBCSR_TPU_MM_FORMAT; ref MM_DENSE + the decision at
    #      dbcsr_mm.F:593-617) ----
    # per-product execution format: "auto" (the planner picks between
    # the BCSR shape-bucketed stack path and the whole-panel padded
    # dense GEMM from the operands' occupancy and dtype), or a forced
    # "stack"/"dense" (A/B legs and the safe engine; a forced dense the
    # engine cannot run — a mesh that is not square — falls back to
    # stack with reason="ineligible")
    mm_format: str = "auto"
    # use the fused pallas SMM kernel when available (ref: libsmm_acc JIT
    # kernels vs cuBLAS loop)
    use_pallas: bool = True
    # validate pallas kernels against the XLA path on first use per
    # (m,n,k,dtype), like libsmm_acc's JIT-time checksum validation
    # (libsmm_acc.cpp:216)
    validate_kernels: bool = True
    # lay A/B out as (N, m*k) flat rows before the per-entry gather so
    # gathers move lane-packed rows instead of tile-padded blocks
    # (see acc/smm.py:_process_stack_xla_flat)
    flat_gather: bool = False
    # fused superstack launches (acc/smm.py:execute_superstack): all
    # spans sharing a destination C bin lower into ONE donated-C
    # program — "auto" (fuse whenever a bin's spans can), "fused"
    # (same, explicit), or "per_span" (the historical one-dispatch-
    # per-span engine).  Env: DBCSR_TPU_SUPERSTACK.
    superstack: str = "auto"
    # distributed Cannon tick scheduling (parallel/cannon.py +
    # parallel/sparse_dist.py): "double_buffer" issues tick k+1's A/B
    # ring shifts against a second operand buffer BEFORE tick k's
    # contraction is consumed (per-tick dispatches; the comm-thread
    # overlap of the reference's async isend/irecv panel exchange,
    # dbcsr_mpiwrap.F:305-421), "serial" is the bitwise-reference
    # single-program shift-after-compute path, "auto" double-buffers
    # whenever the grid actually ring-shifts (s > 1 square Cannon).
    # Env: DBCSR_TPU_CANNON_OVERLAP.
    cannon_overlap: str = "auto"
    # keep per-(m,n,k) flop statistics (ref STATISTICS block)
    keep_stats: bool = True
    # largest block dim the fused Pallas kernel handles; bigger blocks
    # take the XLA dot path (ref max_kernel_dim=80 with cuBLAS-loop
    # fallback, dbcsr_config.F:177, libsmm_acc.cpp:227-249)
    max_kernel_dim: int = 256
    # multiplier on the TAS split-factor estimate
    # (ref TAS_SPLIT_FACTOR, dbcsr_config.F:170)
    tas_split_factor: float = 1.0
    # default 2.5D k-layer count for auto-built meshes
    # (ref NUM_LAYERS_3D, dbcsr_config.F:152); 0 = auto (largest square),
    # any value >= 1 is honored exactly (1 forces a 2D grid and raises
    # when the device count is not a square)
    num_layers_3d: int = 0
    # ---- serving plane (dbcsr_tpu.serve; env DBCSR_TPU_SERVE_*) ----
    # bound on queued requests; beyond it submissions shed queue_full
    serve_queue_max: int = 256
    # cross-request batching window: how long the worker waits for
    # more same-structure requests after popping one (0 disables the
    # wait; coalescing then only groups requests already queued)
    serve_window_ms: float = 5.0
    # master switch for block-diagonal composite execution; off =
    # every request runs serialized (the A/B control leg)
    serve_coalesce: bool = True
    # largest request group one composite multiply may carry
    serve_coalesce_max: int = 8
    # per-tenant quota: queued + running requests
    serve_tenant_inflight: int = 8
    # per-tenant quota: operand bytes queued (a+b+c device bytes)
    serve_tenant_bytes: int = 256 * 1024 * 1024
    # deadline assigned under a DEGRADED health verdict when the
    # request didn't bring its own (seconds)
    serve_degraded_deadline_s: float = 10.0
    # ---- end-to-end data integrity (acc/abft.py; env DBCSR_TPU_ABFT) --
    # ABFT probe checksums at the stack/superstack boundary: "off" (no
    # checks — the production default), "verify" (rank-1 C·v vs
    # A·(B·v) probe per launch; a mismatch classifies `sdc`, feeds the
    # per-(driver, shape) breaker and re-executes down the failover
    # chain), "recover" (verify, plus every recovery re-execution is
    # itself probe-checked before being accepted).  The knob also arms
    # the chain-invariant rollback in models/ and the serving plane's
    # per-request probe (docs/resilience.md § ABFT).
    abft: str = "off"
    # ---- mixed-precision block GEMMs (acc/precision.py; env
    #      DBCSR_TPU_PRECISION) ----
    # compute-dtype policy of the stack engine: "native" (every stack
    # executes at the request dtype — the historical engine), "adaptive"
    # (demote eligible stacks to a narrower compute dtype with
    # wide-dtype accumulation, certified per launch by the ABFT probe
    # and promoted back per (m,n,k,dtype) cell when a probe residual
    # breaches its demotion ceiling or an ops chain tightens past the
    # demoted error floor; inert unless the ABFT plane is on), "f32" /
    # "bf16" (force the demoted compute dtype with two-product
    # compensation, no certification requirement — benchmark/test legs)
    precision: str = "native"
    # ---- delta-aware incremental multiply (mm/incremental.py; env
    #      DBCSR_TPU_INCREMENTAL) ----
    # "auto" (delta-aware: a repeated beta==0 product whose operands
    # carry a known dirty-block delta recomputes only the affected C
    # blocks and splices the rest from the cached device-resident
    # result — bitwise-identical by construction), "off" (machinery
    # fully disabled, zero overhead — the historical engine), "full"
    # (track deltas and maintain the result cache but always recompute
    # fully: the A/B control leg that carries the bookkeeping cost)
    incremental: str = "auto"
    # ---- serve-layer content-addressed product cache (serve/
    #      product_cache.py; env DBCSR_TPU_SERVE_PRODUCT_CACHE*) ----
    # identical (A, B, scalars, flags) submissions — keyed by VALUE
    # digests, invalidated through the mutation-epoch machinery —
    # return the cached C without an engine dispatch
    serve_product_cache: bool = True
    serve_product_cache_entries: int = 32
    serve_product_cache_bytes: int = 128 * 1024 * 1024
    # platform-injection seam (VERDICT r4 item 5): "" = the real JAX
    # backend platform; "tpu"/"cpu" makes every dispatch DECISION
    # (_pallas_supported, the format planner, emulated-dtype R-tiling)
    # behave as if running there, so the CPU suite can assert TPU-only
    # dispatch branches without hardware.  Execution-level choices
    # (pallas interpret=, device placement) always follow the REAL
    # platform — the seam steers policy, never lowering, so a faked
    # "tpu" still runs correctly (if non-production-shaped) on CPU.
    # Analog of the careful-mode dispatch asserts the reference keeps
    # testable off-GPU (dbcsr_mm_sched.F:295-321).
    platform_override: str = ""

    def validate(self) -> None:
        if self.platform_override not in ("", "tpu", "cpu"):
            raise ValueError(
                f"platform_override must be ''/'tpu'/'cpu', "
                f"got {self.platform_override!r}")
        if self.mm_driver not in ("auto", "xla", "xla_group", "pallas",
                                  "pallas_cross", "host"):
            raise ValueError(f"unknown mm_driver {self.mm_driver!r}")
        if self.mm_format not in ("auto", "stack", "dense"):
            raise ValueError(
                f"mm_format must be 'auto'/'stack'/'dense', "
                f"got {self.mm_format!r}")
        if self.superstack not in ("auto", "fused", "per_span"):
            raise ValueError(
                f"superstack must be 'auto'/'fused'/'per_span', "
                f"got {self.superstack!r}")
        if self.cannon_overlap not in ("auto", "double_buffer", "serial"):
            raise ValueError(
                f"cannon_overlap must be 'auto'/'double_buffer'/'serial', "
                f"got {self.cannon_overlap!r}")
        if self.mm_stack_size <= 0:
            raise ValueError("mm_stack_size must be positive")
        if self.max_kernel_dim <= 0:
            raise ValueError("max_kernel_dim must be positive")
        if self.tas_split_factor <= 0:
            raise ValueError("tas_split_factor must be positive")
        if self.num_layers_3d < 0:
            raise ValueError("num_layers_3d must be >= 0")
        if self.serve_queue_max <= 0:
            raise ValueError("serve_queue_max must be positive")
        if self.serve_window_ms < 0:
            raise ValueError("serve_window_ms must be >= 0")
        if self.serve_coalesce_max < 1:
            raise ValueError("serve_coalesce_max must be >= 1")
        if self.serve_tenant_inflight <= 0:
            raise ValueError("serve_tenant_inflight must be positive")
        if self.serve_tenant_bytes <= 0:
            raise ValueError("serve_tenant_bytes must be positive")
        if self.serve_degraded_deadline_s <= 0:
            raise ValueError("serve_degraded_deadline_s must be positive")
        if self.abft not in ("off", "verify", "recover"):
            raise ValueError(
                f"abft must be 'off'/'verify'/'recover', got {self.abft!r}")
        if self.precision not in ("native", "adaptive", "f32", "bf16"):
            raise ValueError(
                f"precision must be 'native'/'adaptive'/'f32'/'bf16', "
                f"got {self.precision!r}")
        if self.incremental not in ("auto", "off", "full"):
            raise ValueError(
                f"incremental must be 'auto'/'off'/'full', "
                f"got {self.incremental!r}")
        if self.serve_product_cache_entries < 1:
            raise ValueError("serve_product_cache_entries must be >= 1")
        if self.serve_product_cache_bytes <= 0:
            raise ValueError("serve_product_cache_bytes must be positive")


_cfg = Config()


def _apply_env(cfg: Config) -> None:
    for f in dataclasses.fields(Config):
        env = os.environ.get(f"DBCSR_TPU_{f.name.upper()}")
        if env is None:
            continue
        if isinstance(getattr(cfg, f.name), bool):
            setattr(cfg, f.name, env.lower() in ("1", "true", "yes"))
        elif isinstance(getattr(cfg, f.name), int):
            setattr(cfg, f.name, int(env))
        elif isinstance(getattr(cfg, f.name), float):
            setattr(cfg, f.name, float(env))
        else:
            setattr(cfg, f.name, env)
    # fail FAST on a typo'd env knob (DBCSR_TPU_SUPERSTACK=per-span,
    # DBCSR_TPU_MM_DRIVER=xla_grp, ...): silently running a different
    # configuration than the operator asked for poisons A/B evidence —
    # the same contract set_config enforces for programmatic updates
    cfg.validate()


_apply_env(_cfg)


def get_config() -> Config:
    return _cfg


def set_config(**kwargs) -> None:
    """Programmatic config update (ref `dbcsr_set_config`).

    Validates on a candidate copy first: a rejected update must leave
    the live config untouched."""
    for k in kwargs:
        if not hasattr(_cfg, k):
            raise ValueError(f"unknown config key {k!r}")
    candidate = dataclasses.replace(_cfg, **kwargs)
    candidate.validate()
    for k, v in kwargs.items():
        setattr(_cfg, k, v)


def print_config(out=print) -> None:
    """Ref `dbcsr_print_config`."""
    for f in dataclasses.fields(Config):
        out(f"  dbcsr_tpu.{f.name:<28} {getattr(_cfg, f.name)}")


def effective_platform() -> str:
    """The platform dispatch DECISIONS key on: `platform_override` when
    set (the CPU suite's seam for asserting TPU-only branches), else
    the real JAX backend platform.  Execution-level code (interpret=
    flags, device placement) must NOT use this — it reads the real
    platform directly, so an override never changes lowering."""
    if _cfg.platform_override:
        return _cfg.platform_override
    import jax

    return jax.devices()[0].platform


def get_default_config() -> Config:
    """A fresh Config with compile-time defaults — env overrides NOT
    applied (ref `dbcsr_get_default_config`, `dbcsr_api.F:175`)."""
    return Config()
