"""Candidate-cell miner: rank underperforming kernel cells by wasted
FLOP-seconds.

Two evidence sources, merged:

* the LIVE telemetry history store (`obs.timeseries`): the
  per-(driver, mnk, dtype) flop cells (``dbcsr_tpu_cell_flops_total``)
  joined against their driver's achieved-GFLOP/s and roofline-fraction
  series — the exact substrate PR 11 built for this consumer;
* capture files the caller names (JSONL of `acc.bench` kernel rows):
  per-kernel micro-benchmark rows whose measured GFLOP/s (or embedded
  ``modeled.roofline_fraction``) sit below the floor.

A cell is *underperforming* when its driver's roofline fraction is
below the per-device floor (``DBCSR_TPU_TUNE_FLOOR``, default 0.25) or
when `acc.params.predict`'s donor estimate says tuned parameters
already achieved materially more on a neighboring shape.  Candidates
are ranked by **wasted FLOP-seconds** — the seconds the observed flops
would have saved at the target rate:

    wasted = flops/1e9 * (1/observed_gflops - 1/target_gflops)

so the tuner always works the most expensive cell first, not the
slowest one.  The queue is bounded by ``DBCSR_TPU_TUNE_MAX_CELLS``
(default 32) and surfaced as the ``dbcsr_tpu_tune_queue_depth`` gauge.

Stdlib-only at import; jax/obs layers are reached lazily.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional

from dbcsr_tpu.tune._env import env_float as _env_float
from dbcsr_tpu.tune._env import env_int as _env_int

_MNK_RE = re.compile(r"^(\d+)x(\d+)x(\d+)$")

# a donor prediction only sets the target when it beats the observed
# rate by this much (noise floor; mirrors the service promotion margin)
_PREDICT_MARGIN = 0.10


def floor() -> float:
    return _env_float("DBCSR_TPU_TUNE_FLOOR", 0.25)


def max_cells() -> int:
    return max(1, _env_int("DBCSR_TPU_TUNE_MAX_CELLS", 32))


def _predict_gflops(m: int, n: int, k: int, dtype,
                    stack_size: Optional[int]) -> Optional[float]:
    """What tuned evidence (exact or donor row) says this cell can do —
    the miner's target when it beats the observed rate."""
    try:
        from dbcsr_tpu.acc import params as params_mod

        row = params_mod.predict(m, n, k, dtype, stack_size=stack_size)
        if row and row.get("gflops"):
            return float(row["gflops"])
    except Exception:
        pass
    return None


def _production_stack_size() -> int:
    try:
        from dbcsr_tpu.core.config import get_config

        return int(get_config().mm_stack_size)
    except Exception:
        return 30000


def _wasted(flops: float, observed: float, target: float) -> float:
    if observed <= 0 or target <= observed:
        return 0.0
    return flops / 1e9 * (1.0 / observed - 1.0 / target)


def _mine_timeseries(query) -> List[Dict]:
    """Candidates from the live (or replayed) telemetry rings."""
    out: List[Dict] = []
    try:
        cells = query("dbcsr_tpu_cell_flops_total", agg="last")
        ach = {r["labels"].get("driver"): r.get("value")
               for r in query("dbcsr_tpu_achieved_gflops", agg="last")}
        frac = {r["labels"].get("driver"): r.get("value")
                for r in query("dbcsr_tpu_roofline_fraction", agg="last")}
    except Exception:
        return out
    fl = floor()
    stack_size = _production_stack_size()
    for row in cells:
        labels = row.get("labels", {})
        mm = _MNK_RE.match(str(labels.get("mnk", "")))
        driver = labels.get("driver")
        dtype = labels.get("dtype", "float64")
        flops = row.get("value")
        if mm is None or driver is None or not flops:
            continue
        m, n, k = (int(x) for x in mm.groups())
        observed = ach.get(driver)
        f = frac.get(driver)
        if not observed or observed <= 0:
            continue
        predicted = _predict_gflops(m, n, k, dtype, stack_size)
        reasons = []
        target = 0.0
        if f is not None and f < fl:
            # below the floor: the attainable rate at the floor is the
            # minimum acceptable target
            target = observed * fl / max(f, 1e-9)
            reasons.append(f"roofline {f:.4f} < floor {fl}")
        if predicted and predicted > observed * (1.0 + _PREDICT_MARGIN):
            target = max(target, predicted)
            reasons.append(
                f"donor prediction {predicted:.3g} GFLOP/s > observed "
                f"{observed:.3g}")
        if not reasons:
            continue
        out.append({
            "m": m, "n": n, "k": k, "dtype": dtype, "driver": driver,
            "stack_size": stack_size,
            "observed_gflops": round(float(observed), 4),
            "target_gflops": round(float(target), 4),
            "wasted_flop_seconds": _wasted(float(flops), float(observed),
                                           float(target)),
            "flops": float(flops),
            "source": "timeseries",
            "reason": "; ".join(reasons),
        })
    return out


def _capture_rows(path: str) -> List[Dict]:
    rows = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    continue  # torn tail line
    except OSError:
        pass
    return rows


def _mine_captures(paths) -> List[Dict]:
    """Candidates from capture files: per-kernel rows
    with a measured GFLOP/s (acc micro-bench schema) whose modeled
    roofline fraction — or donor-predicted rate — shows headroom."""
    out: List[Dict] = []
    fl = floor()
    for path in paths:
        for rec in _capture_rows(path):
            mm = _MNK_RE.match(str(rec.get("kernel", "")))
            gflops = rec.get("gflops") or rec.get("value")
            if mm is None or not isinstance(gflops, (int, float)) \
                    or gflops <= 0:
                continue
            m, n, k = (int(x) for x in mm.groups())
            dtype = str(rec.get("dtype", "float64"))
            stack_size = int(rec.get("stack_size", 0)) or \
                _production_stack_size()
            modeled = rec.get("modeled") or {}
            f = modeled.get("roofline_fraction")
            predicted = _predict_gflops(m, n, k, dtype, stack_size)
            reasons = []
            target = 0.0
            if f is not None and f < fl:
                target = float(gflops) * fl / max(float(f), 1e-9)
                reasons.append(f"roofline {f:.4f} < floor {fl}")
            if predicted and predicted > gflops * (1.0 + _PREDICT_MARGIN):
                target = max(target, predicted)
                reasons.append(
                    f"donor prediction {predicted:.3g} GFLOP/s > "
                    f"measured {gflops:.3g}")
            if not reasons:
                continue
            # one committed row's worth of work is the capture's weight
            flops = 2.0 * m * n * k * stack_size
            out.append({
                "m": m, "n": n, "k": k, "dtype": dtype,
                "driver": rec.get("driver", "auto"),
                "stack_size": stack_size,
                "observed_gflops": round(float(gflops), 4),
                "target_gflops": round(float(target), 4),
                "wasted_flop_seconds": _wasted(flops, float(gflops),
                                               float(target)),
                "flops": flops,
                "source": os.path.basename(path),
                "reason": "; ".join(reasons),
            })
    return out


def mine(limit: Optional[int] = None, query=None,
         capture_paths=()) -> List[Dict]:
    """The ranked candidate-cell queue, most wasted FLOP-seconds first.

    ``query`` defaults to the live `obs.timeseries.query`;
    ``capture_paths`` names capture files to mine beside the telemetry
    (none by default).  Duplicate (m, n, k, dtype) cells keep the
    most-wasteful sighting."""
    if query is None:
        from dbcsr_tpu.obs import timeseries as ts

        query = ts.query
    cands = _mine_timeseries(query) + _mine_captures(capture_paths)
    best: Dict[tuple, Dict] = {}
    for c in cands:
        key = (c["m"], c["n"], c["k"], c["dtype"])
        cur = best.get(key)
        if cur is None or c["wasted_flop_seconds"] > \
                cur["wasted_flop_seconds"]:
            best[key] = c
    ranked = sorted(best.values(),
                    key=lambda c: -c["wasted_flop_seconds"])
    ranked = ranked[:max_cells() if limit is None else limit]
    try:
        from dbcsr_tpu.obs import metrics

        metrics.gauge(
            "dbcsr_tpu_tune_queue_depth",
            "mined underperforming-cell queue depth (dbcsr_tpu.tune)",
        ).set(len(ranked))
    except Exception:
        pass
    return ranked
