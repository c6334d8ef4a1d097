"""Deterministic, seeded fault injection at the engine's trust
boundaries.

Without it the only way to exercise the engine's failure handling is
a real hardware fault, and nothing in CI walks the recovery paths.
This module makes every failure kind the TPU path has actually
produced injectable on CPU, deterministically, so
`tests/test_resilience.py` and `tools/chaos_suite.py` can drive the
failover/breaker/watchdog machinery without hardware.

**Sites** (where `maybe_inject`/`corrupt` hooks are registered):

========================  ====================================================
site                      boundary
========================  ====================================================
``execute_stack``         `acc.smm.execute_stack`, per driver launch
                          (labels: ``driver``)
``prepare_stack``         `acc.smm.prepare_stack` (driver selection)
``dense``                 the dense paths in `mm.multiply`
``multihost_init``        `parallel.multihost.init_multihost`
``collective``            `parallel.sparse_dist` mesh dispatch boundary
``mesh_shift``            the double-buffered Cannon tick/shift
                          boundary (`parallel.overlap.run_ticks`, one
                          per ring shift; labels: ``engine``,
                          ``tick``) — a fault here degrades the
                          multiply to the serial fused program
``gather_chunk``          the chunked all-gather pipeline's per-shard
                          ring-step boundary on rectangular grids
                          (same `run_ticks` edge, driver
                          ``gather_pipe``; labels: ``engine``,
                          ``tick``) — degrades to the fused
                          one-collective program
``tas_tick``              the staggered grouped-TAS metronome's
                          tick/shift boundary (same `run_ticks` edge,
                          driver ``cannon_db`` keyed engine="tas") —
                          degrades to the fused lockstep program
``serve_admit``           `serve.queue.AdmissionQueue.admit` — a fault
                          here sheds the submission with a structured
                          rejection (labels: ``tenant``,
                          ``request_id``)
``serve_execute``         the serving worker's group-execution
                          boundary (`serve.engine`) — a fault on a
                          coalesced group degrades it to serialized
                          per-request execution; on a lone request it
                          fails that request TRANSIENT (labels:
                          ``request_id``, ``n``)
========================  ====================================================

A spec's *target* matches either the site name or a label value (the
driver name), so ``pallas:raise`` fires only on pallas launches while
``execute_stack:raise`` fires on every driver.

**Kinds**: ``raise`` (XlaRuntimeError), ``oom`` (RESOURCE_EXHAUSTED —
the transient classification the demotion handlers key on), ``nan``
(corrupt the output blocks with NaN — caught by the post-execution
output check), ``flip`` (perturb one output element by a large but
FINITE seed-deterministic delta — the silent-data-corruption model:
invisible to every finite-output check, detectable only by the ABFT
probe / chain-invariant layer, ``DBCSR_TPU_ABFT``), ``hang`` (sleep
past a deadline, default ``sleep=30``), ``fail`` (generic `FaultError`
failure).

**DSL** (``DBCSR_TPU_FAULTS``): specs separated by ``;``::

    target:kind[@stack{>=,<=,==,<,>}N][,prob=P][,seed=S][,times=N][,sleep=SEC]

    pallas:raise@stack>=3,prob=0.5,seed=7   # from the 3rd pallas
                                            # launch, coin-flip (seeded)
    dense:nan,times=1                       # corrupt one dense product
    serve_admit:fail,times=3                # shed three submissions
    multihost_init:hang,sleep=5             # wedge the world join 5 s

``@stack>=N`` conditions on the per-spec *matching-call counter* (1 on
the first matching call).  ``times=N`` caps how often the spec fires —
a wedge streak that then heals.  ``prob`` draws from a per-spec
`random.Random(seed)`, so schedules replay bit-identically.

Activation: the env var is parsed on first use; tests use
`inject_faults(...)` (a context manager) or `configure`/`clear`.  When
no spec is configured, every hook is one module-attribute truth check
(`active()`), keeping the disabled path inside the existing
≤10 µs/multiply budget.

Stdlib-only at import; jax is reached lazily (error type, NaN
corruption).
"""

from __future__ import annotations

import contextlib
import os
import random
import re
import threading
import time
from typing import List, Optional

_lock = threading.Lock()
_specs: List["FaultSpec"] = []
_env_parsed = False

KINDS = ("raise", "oom", "nan", "hang", "fail", "flip")


class FaultError(RuntimeError):
    """Raised for injected ``fail`` faults (and as the fallback when
    the real XlaRuntimeError type is unavailable)."""


def _xla_error_type():
    """The runtime error type a real failing device launch raises —
    injected faults must walk the exact same except-clauses."""
    try:
        import jax

        return jax.errors.JaxRuntimeError
    except Exception:  # jax absent / too old: a stand-in is fine
        return FaultError


_SPEC_RE = re.compile(
    r"^(?P<target>[A-Za-z0-9_.]+):(?P<kind>[a-z]+)"
    r"(?:@stack(?P<op>>=|<=|==|<|>)(?P<n>\d+))?$"
)


class FaultSpec:
    """One parsed fault rule (see the module docstring for the DSL)."""

    __slots__ = ("target", "kind", "op", "n", "prob", "seed", "times",
                 "sleep", "calls", "fired", "_rng")

    def __init__(self, target: str, kind: str, op: str = ">=", n: int = 0,
                 prob: float = 1.0, seed: int = 0,
                 times: Optional[int] = None, sleep: float = 30.0):
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r} (one of {KINDS})")
        self.target = target
        self.kind = kind
        self.op = op
        self.n = n
        self.prob = prob
        self.seed = seed
        self.times = times
        self.sleep = sleep
        self.calls = 0   # matching calls seen
        self.fired = 0   # faults actually injected
        self._rng = random.Random(seed)

    def _cond_ok(self) -> bool:
        c, n = self.calls, self.n
        return {
            ">=": c >= n, "<=": c <= n, "==": c == n,
            "<": c < n, ">": c > n,
        }[self.op]

    def matches(self, site: str, labels: dict) -> bool:
        return self.target == site or self.target in labels.values()

    def should_fire(self) -> bool:
        """Advance the matching-call counter and decide (deterministic
        given the seed and call sequence)."""
        self.calls += 1
        if not self._cond_ok():
            return False
        if self.times is not None and self.fired >= self.times:
            return False
        if self.prob < 1.0 and self._rng.random() >= self.prob:
            return False
        self.fired += 1
        return True

    def __repr__(self):
        cond = f"@stack{self.op}{self.n}" if self.n else ""
        return (f"FaultSpec({self.target}:{self.kind}{cond},"
                f"prob={self.prob},seed={self.seed},times={self.times})")


def parse(spec_string: str) -> List[FaultSpec]:
    """Parse a ``DBCSR_TPU_FAULTS`` value into FaultSpecs."""
    specs = []
    for part in spec_string.split(";"):
        part = part.strip()
        if not part:
            continue
        head, *opts = part.split(",")
        m = _SPEC_RE.match(head.strip())
        if m is None:
            raise ValueError(
                f"bad fault spec {head!r} (want target:kind[@stack>=N])")
        kw = dict(target=m.group("target"), kind=m.group("kind"))
        if m.group("op"):
            kw["op"], kw["n"] = m.group("op"), int(m.group("n"))
        for o in opts:
            k, _, v = o.strip().partition("=")
            if k == "prob":
                kw["prob"] = float(v)
            elif k == "seed":
                kw["seed"] = int(v)
            elif k == "times":
                kw["times"] = int(v)
            elif k == "sleep":
                kw["sleep"] = float(v)
            else:
                raise ValueError(f"unknown fault option {k!r} in {part!r}")
        specs.append(FaultSpec(**kw))
    return specs


def configure(spec_string: Optional[str]) -> List[FaultSpec]:
    """Install a fault schedule (replacing any active one); None/""
    clears it."""
    global _specs, _env_parsed
    with _lock:
        _env_parsed = True  # explicit configuration overrides the env
        _specs = parse(spec_string) if spec_string else []
        return _specs


def clear() -> None:
    configure(None)


def _ensure_env() -> None:
    global _env_parsed
    if _env_parsed:
        return
    with _lock:
        if _env_parsed:
            return
        env = os.environ.get("DBCSR_TPU_FAULTS")
        if env:
            _specs.extend(parse(env))
        _env_parsed = True


def active() -> bool:
    """True when any fault spec is installed.  THE hot-path gate: call
    sites guard every other function in this module behind it."""
    if not _env_parsed:
        _ensure_env()
    return bool(_specs)


def specs() -> List[FaultSpec]:
    _ensure_env()
    return list(_specs)


def _note(site: str, spec: FaultSpec, labels: dict) -> None:
    """Every injected fault is observable: trace instant + counter +
    flight-recorder event."""
    import sys

    if "dbcsr_tpu.obs.metrics" not in sys.modules:
        # never be the cause of the first obs import — an env-activated
        # trace session must only open in engine processes
        return
    try:
        from dbcsr_tpu.obs import events as _events
        from dbcsr_tpu.obs import metrics as _metrics

        _metrics.counter(
            "dbcsr_tpu_faults_injected_total",
            "faults injected by dbcsr_tpu.resilience.faults per site/kind",
        ).inc(site=site, kind=spec.kind)
        # one publish = bus record (product-correlated) + trace instant
        # + flight event, replacing the three hand-rolled emissions
        _events.publish(
            "fault_injected",
            {"site": site, "kind": spec.kind, "target": spec.target,
             "fired": spec.fired,
             **{k: str(v) for k, v in labels.items()}},
            flight=("fault_injected", {"site": site, "kind": spec.kind,
                                       "target": spec.target}),
        )
    except Exception:
        pass  # observability must never turn an injected fault into a real one


def _firing_spec(site: str, kinds, labels: dict) -> Optional[FaultSpec]:
    for spec in _specs:
        if spec.kind in kinds and spec.matches(site, labels):
            if spec.should_fire():
                return spec
    return None


def maybe_inject(site: str, **labels) -> None:
    """Raise/sleep if a configured ``raise``/``oom``/``fail``/``hang``
    fault fires at this site.  No-op (after the `active()` gate the
    call sites apply) when nothing matches."""
    if not _specs:
        return
    spec = _firing_spec(site, ("raise", "oom", "fail", "hang"), labels)
    if spec is None:
        return
    _note(site, spec, labels)
    if spec.kind == "hang":
        time.sleep(spec.sleep)
        return
    if spec.kind == "fail":
        raise FaultError(f"injected fault at {site} ({spec!r})")
    err = _xla_error_type()
    if spec.kind == "oom":
        raise err(
            f"RESOURCE_EXHAUSTED: injected device OOM at {site} "
            f"(fault injection, {spec.target})")
    raise err(
        f"INTERNAL: injected XlaRuntimeError at {site} "
        f"(fault injection, {spec.target})")


def corrupt(site: str, value, **labels):
    """Apply a configured ``nan``/``flip`` corruption to a device array
    (the simulated bad-kernel output).  Returns ``value`` unchanged
    when no spec fires.

    ``nan`` poisons one element with NaN (caught by the finite-output
    check); ``flip`` adds a large FINITE seed-deterministic delta to
    one element — the silent-data-corruption model that only the ABFT
    probe / chain-invariant layer can see."""
    if not _specs:
        return value
    spec = _firing_spec(site, ("nan", "flip"), labels)
    if spec is None:
        return value
    _note(site, spec, labels)
    import jax.numpy as jnp

    flat = jnp.ravel(value)
    if flat.size == 0 or not jnp.issubdtype(value.dtype, jnp.inexact):
        return value
    # poison a deterministic element so the corruption is reproducible
    idx = spec.seed % int(flat.size)
    if spec.kind == "flip":
        # large-but-finite, exactly representable in every engine dtype
        # (bf16 included), deterministic per (seed): a bit-flip-scale
        # perturbation far above any ABFT tolerance floor
        delta = float(1 << 10) + float(spec.seed % 997)
        return jnp.reshape(flat.at[idx].add(
            jnp.asarray(delta, dtype=flat.dtype)), value.shape)
    return jnp.reshape(flat.at[idx].set(jnp.nan), value.shape)


@contextlib.contextmanager
def inject_faults(spec_string: str):
    """Context-manager API for tests: install a schedule, restore the
    previous one on exit.

        with inject_faults("pallas:raise,times=1"):
            multiply(...)  # first pallas launch raises, failover runs
    """
    global _specs
    _ensure_env()
    with _lock:
        prev = list(_specs)
    installed = configure(spec_string)
    try:
        yield installed
    finally:
        with _lock:
            _specs = prev
