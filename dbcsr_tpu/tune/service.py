"""The autotuning service loop: mine → trial → promote, continuously.

One `cycle()` is the whole closed loop, synchronous and deterministic
(the tested form; the background thread just paces cycles on
``DBCSR_TPU_TUNE_INTERVAL_S``):

1. **admission gate** — the cycle runs only while
   `obs.health.admission_status()` is OK: a DEGRADED/CRITICAL process
   must spend its capacity on traffic, not trials (the same verdict
   the serve plane keys admission on, so the tuner can never compete
   with a struggling worker);
2. **regression judge** — `store.check_regressions()` first: a
   promoted row whose live roofline cell collapsed is demoted before
   any new work starts;
3. **mine** — `miner.mine()` ranks underperforming cells by wasted
   FLOP-seconds; the top cell gets this cycle's trial;
4. **trial** — `trials.run_trial()` (watchdog-guarded, byte/wall
   budgets, ``tune_trial`` fault boundary).  A non-OK trial promotes
   NOTHING — ever;
5. **promote** — the breaker-aware winner is promoted through
   `store.promote` only when it beats the incumbent evidence by
   ``DBCSR_TPU_TUNE_MARGIN`` (default 5%).  The promotion bumps the
   params generation, retiring every stale plan.

A side channel rides each cycle: `store.peer_sync` adopts
same-device-kind peers' promotions over the fleet tier
(``DBCSR_TPU_FLEET_PEERS``) so one worker's trial pays for the whole
fleet.  An IDLE cycle (empty kernel queue) just returns: the storage
format is the planner's rule (`mm.format_planner`), not a tuned row.

Lifecycle: `maybe_start_from_env()` starts the background thread when
``DBCSR_TPU_TUNE=1`` (the serve engine calls it at start and
`stop_service` at shutdown); embedding apps construct `TuneService`
directly.  `current_service()` is the obs layers' read seam (health
component, timeseries collector, doctor) — it never CREATES a service.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

from dbcsr_tpu.tune import miner, store, trials
from dbcsr_tpu.tune._env import env_float as _env_float

_lock = threading.Lock()
_service: Optional["TuneService"] = None


class TuneService:
    """The online tuner: one instance per process (module singleton via
    `get_service`), cycles run synchronously or on the background
    thread."""

    def __init__(self, interval_s: Optional[float] = None,
                 kind: Optional[str] = None, seed: int = 7):
        self.interval_s = (_env_float("DBCSR_TPU_TUNE_INTERVAL_S", 60.0)
                           if interval_s is None else float(interval_s))
        self.margin = _env_float("DBCSR_TPU_TUNE_MARGIN", 0.05)
        self.kind = kind
        self.seed = seed
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._state_lock = threading.Lock()
        self.stats: Dict = {
            "cycles": 0, "trials": 0, "promotions": 0, "demotions": 0,
            "deferred": 0, "queue_depth": 0, "last_cycle_s": 0.0,
            "last_outcome": None, "last_error": None,
            "last_cycle_demoted": False,
            "trial_failure_streak": 0,
        }

    # ------------------------------------------------------------ state

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def snapshot(self) -> Dict:
        with self._state_lock:
            snap = dict(self.stats)
        snap["running"] = self.running
        snap["interval_s"] = self.interval_s
        snap["generation"] = store.generation()
        return snap

    def _note(self, **updates) -> None:
        with self._state_lock:
            self.stats.update(updates)

    # ------------------------------------------------------------ cycle

    def cycle(self, cells: Optional[List[Dict]] = None) -> Dict:
        """One mine → trial → promote pass.  Returns the outcome dict
        (also folded into `snapshot()`)."""
        t0 = time.monotonic()
        with self._state_lock:
            self.stats["cycles"] += 1
        out: Dict = {"outcome": "idle", "cell": None, "promoted": None,
                     "demoted": []}
        try:
            out = self._cycle_inner(cells, out)
            self._note(last_error=None)
        except Exception as exc:
            out["outcome"] = "error"
            out["error"] = f"{type(exc).__name__}: {exc}"
            self._note(last_error=out["error"])
        dur = time.monotonic() - t0
        # demotion visibility is its OWN flag: a cycle that demotes a
        # regressed row and then also promotes/fails its trial would
        # otherwise overwrite last_outcome and hide the demotion from
        # the health component's operator page
        self._note(last_cycle_s=round(dur, 4),
                   last_outcome=out["outcome"],
                   last_cycle_demoted=bool(out.get("demoted")))
        try:
            from dbcsr_tpu.obs import metrics

            metrics.gauge(
                "dbcsr_tpu_tune_cycle_seconds",
                "wall seconds of the last online-tuner cycle",
            ).set(round(dur, 4))
        except Exception:
            pass
        return out

    def _admission(self) -> str:
        try:
            from dbcsr_tpu.obs import health

            return health.admission_status()
        except Exception:
            return "OK"

    def _cycle_inner(self, cells, out: Dict) -> Dict:
        admission = self._admission()
        if admission != "OK":
            # a degraded process tunes nothing: trials compete with the
            # traffic that degraded it (serve admission shares this
            # verdict, so the gate can never starve a healthy worker)
            with self._state_lock:
                self.stats["deferred"] += 1
            out["outcome"] = f"deferred:{admission}"
            return out
        demoted = store.check_regressions(kind=self.kind)
        if demoted:
            with self._state_lock:
                self.stats["demotions"] += len(demoted)
            out["demoted"] = demoted
            out["outcome"] = "demoted"
        try:
            # fleet tier: adopt same-device-kind peers' promotions so
            # one worker's trial pays for the whole fleet (bounded
            # per-peer timeout + cool-off inside peer_sync; a peerless
            # process returns [] without any I/O)
            adopted = store.peer_sync(kind=self.kind)
        except Exception:
            adopted = []
        if adopted:
            with self._state_lock:
                self.stats["adoptions"] = \
                    self.stats.get("adoptions", 0) + len(adopted)
            out["adopted"] = adopted
        if cells is None:
            cells = miner.mine()
        self._note(queue_depth=len(cells))
        if not cells:
            return out  # no kernel cell wastes FLOP-seconds: idle
        cell = cells[0]
        out["cell"] = {k: cell.get(k)
                       for k in ("m", "n", "k", "dtype", "stack_size",
                                 "wasted_flop_seconds", "reason")}
        with self._state_lock:
            self.stats["trials"] += 1
        trial = trials.run_trial(cell, seed=self.seed)
        if not trial.ok:
            with self._state_lock:
                self.stats["trial_failure_streak"] += 1
            out["outcome"] = f"trial_{trial.outcome}"
            out["error"] = trial.error
            return out
        self._note(trial_failure_streak=0)
        winner = trials.select_winner(trial.candidates, int(cell["m"]),
                                      int(cell["n"]), int(cell["k"]),
                                      cell.get("dtype", "float64"))
        if winner is None:
            out["outcome"] = "quarantined"
            return out
        promoted = self._maybe_promote(cell, trial, winner)
        if promoted is not None:
            with self._state_lock:
                self.stats["promotions"] += 1
            out["promoted"] = {
                "driver": promoted["entry"].get("driver"),
                "gflops": promoted["entry"].get("gflops"),
                "generation": promoted["generation"],
            }
            out["outcome"] = "promoted"
        elif out["outcome"] != "demoted":
            out["outcome"] = "held"
        return out

    def _incumbent_gflops(self, cell: Dict) -> Optional[float]:
        """The evidence bar a winner must clear: what the cell
        ACHIEVES live (the miner's observed rate).  Deliberately NOT
        the incumbent row's gflops claim — a stale row whose number
        was measured in another life (a different device) must not be able to block its own displacement.  The claim is
        the fallback only when the cell was mined without a live
        rate."""
        obs = cell.get("observed_gflops")
        if isinstance(obs, (int, float)) and obs > 0:
            return float(obs)
        try:
            from dbcsr_tpu.acc import params as params_mod

            row = params_mod.predict(
                int(cell["m"]), int(cell["n"]), int(cell["k"]),
                cell.get("dtype", "float64"),
                stack_size=cell.get("stack_size"))
        except Exception:
            row = None
        claim = (row or {}).get("gflops")
        return float(claim) if isinstance(claim, (int, float)) \
            and claim > 0 else None

    @staticmethod
    def _same_config(winner: Dict, incumbent: Optional[Dict]) -> bool:
        if not incumbent:
            return False
        fields = ("driver", "grouping", "r0", "variant", "pack_p",
                  "precision")
        return all(winner.get(f) == incumbent.get(f) for f in fields)

    def _maybe_promote(self, cell: Dict, trial, winner: Dict):
        from dbcsr_tpu.acc import params as params_mod

        import numpy as np

        m, n, k = int(cell["m"]), int(cell["n"]), int(cell["k"])
        dtype = np.dtype(cell.get("dtype", "float64")).name
        incumbent = params_mod.lookup(m, n, k, dtype,
                                      stack_size=cell.get("stack_size"))
        if self._same_config(winner, incumbent):
            return None  # the table already says this; don't churn plans
        bar = self._incumbent_gflops(cell)
        if bar is not None and winner.get("gflops", 0.0) \
                <= bar * (1.0 + self.margin):
            return None
        base = trial.entry or {}
        row = {
            "m": m, "n": n, "k": k, "dtype": dtype,
            "stack_size": trial.stack_size,
            "env": base.get("env", "cpu"),
            **{f: winner[f] for f in winner
               if f not in ("m", "n", "k", "dtype", "stack_size", "env")},
        }
        row["gflops"] = round(float(winner.get("gflops", 0.0)), 2)
        return store.promote(
            row,
            trial={"stack_size": trial.stack_size,
                   "elapsed_s": round(trial.elapsed_s, 3),
                   "candidates": trial.candidates,
                   "mined": {kk: cell.get(kk) for kk in
                             ("observed_gflops", "target_gflops",
                              "wasted_flop_seconds", "reason",
                              "source")}},
            stack_size=int(cell.get("stack_size", trial.stack_size)),
            kind=self.kind)

    # ------------------------------------------------------- background

    def start(self) -> None:
        """Start the background cycle thread (idempotent)."""
        if self.running:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="dbcsr-tpu-tune", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.cycle()
            except Exception as exc:  # the loop must survive anything
                self._note(last_error=f"{type(exc).__name__}: {exc}")


# -------------------------------------------------------------- module

def get_service(create: bool = True, **kwargs) -> Optional[TuneService]:
    """The process's tuner singleton (created on first call unless
    ``create=False``)."""
    global _service
    with _lock:
        if _service is None and create:
            _service = TuneService(**kwargs)
        return _service


def current_service() -> Optional[TuneService]:
    """The live service or None — the obs read seam (never creates)."""
    return _service


def maybe_start_from_env() -> Optional[TuneService]:
    """Start the background tuner when ``DBCSR_TPU_TUNE`` is truthy
    (the serve engine's start hook).  Returns the service (or None
    when the knob is off)."""
    if os.environ.get("DBCSR_TPU_TUNE", "") not in ("1", "on", "true"):
        return None
    svc = get_service()
    svc.start()
    return svc


def stop_service() -> None:
    """Stop and drop the singleton (serve shutdown, tests)."""
    global _service
    with _lock:
        svc, _service = _service, None
    if svc is not None:
        svc.stop()
