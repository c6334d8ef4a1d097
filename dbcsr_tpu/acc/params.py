"""Autotuned kernel-parameter table.

Analog of `src/acc/libsmm_acc/parameters/parameters_<GPU>.json` (+
`parameters_utils.h` lookup): per-(m, n, k, dtype) tuned launch
parameters for the stack kernel, keyed by device kind.  Entries are
produced by `dbcsr_tpu.acc.tune` and consulted at dispatch time — the
role the reference's per-GPU JSON plays for `libsmm_acc_process`
(`libsmm_acc.cpp:227-249` parameter lookup on kernel-cache miss).

Schema per entry: {"m", "n", "k", "dtype", "stack_size",
"driver": "pallas"|"xla"|..., "grouping", "gflops", and optionally
"precision": "native"|"f32"|"f32c"|"bf16"|"bf16c" — the per-cell
compute-dtype column `acc.precision.resolve` consults in adaptive
mode ("native" pins the cell to full precision, "f32"/"bf16" name the
demoted compute dtype with a trailing "c" selecting the two-product-
compensated kernel — the tuner ranks compensated and uncompensated as
separate candidates, so the column carries which one won; absent =
the platform default policy)}, and "env": "onchip"|"cpu" — where the
tuner measured the row (provenance only; each device kind has its own
file).  Rows are keyed by (m, n, k, dtype, stack_size): the same shape
tuned at S=30k and S=800k keeps BOTH rows (small-stack timings are
launch-bound and would otherwise clobber production-scale rows), and
dispatch picks the row nearest the live stack size.
"""

from __future__ import annotations

import functools
import json
import os
import re
import threading
from typing import Dict, Optional

_lock = threading.Lock()
_cache: Dict[str, Dict] = {}
_table_gen = 0  # bumped by save_entry; guards predict memoization
# (path, generation) -> {(m, n, k, dtype): [entries]}; one generation kept
_shape_index: Dict[tuple, Dict] = {}


def _by_shape(path: str, table: Dict) -> Dict:
    """Secondary index over the table for O(1) per-shape row lists
    (lookup sits on the multiply hot path via predict)."""
    key = (path, _table_gen)
    with _lock:
        idx = _shape_index.get(key)
        if idx is None:
            idx = {}
            for e in table.values():
                idx.setdefault(
                    (e["m"], e["n"], e["k"], e["dtype"]), []
                ).append(e)
            _shape_index.clear()
            _shape_index[key] = idx
    return idx


def _params_dir() -> str:
    """Writable parameter directory: $DBCSR_TPU_PARAMS_DIR overrides the
    in-package default (which may be read-only in an installed tree)."""
    return os.environ.get(
        "DBCSR_TPU_PARAMS_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "params"),
    )


@functools.lru_cache(maxsize=4)
def _device_kind_real() -> str:
    import jax

    return re.sub(r"\W+", "_", jax.devices()[0].device_kind).strip("_")


def device_kind() -> str:
    """Device kind keying the parameter table.  Under the CPU suite's
    platform_override seam a PRETEND platform must not consume the real
    device's tuned rows (a cpu-kind "host" row would steer pretend-TPU
    dispatch to a driver the real TPU never uses), so overrides that
    differ from the real platform get their own (normally empty) kind."""
    import jax

    from dbcsr_tpu.core.config import get_config

    ov = get_config().platform_override
    if ov and ov != jax.devices()[0].platform:
        return f"pretend_{ov}"
    return _device_kind_real()


def params_path(kind: Optional[str] = None) -> str:
    return os.path.join(_params_dir(), f"parameters_{kind or device_kind()}.json")


def _key(m: int, n: int, k: int, dtype, stack_size) -> str:
    import numpy as np

    return f"{m}x{n}x{k}:{np.dtype(dtype).name}:{int(stack_size)}"


def generation() -> int:
    """The parameter-table generation counter: bumped by `save_entry`,
    `delete_entry` and `invalidate`.  Plan caches that bake tuned
    parameters into a cached plan (``mm/multiply``'s `_plan_cache`, the
    fused superstack decisions cached next to it) key on this value, so
    a promotion/demotion by the online tuner (`dbcsr_tpu.tune`) retires
    every stale plan at its next lookup — no plan ever serves old
    parameters."""
    return _table_gen


def invalidate() -> int:
    """Drop the module-level table caches and bump the generation.

    The promotion seam for writers that bypass `save_entry` (the tune
    store's atomic file replace, an external tuner process updating the
    params dir): without it a process keeps serving the in-memory table
    it loaded at import forever.  Returns the new generation."""
    global _table_gen
    with _lock:
        _cache.clear()
        _shape_index.clear()
        _predict_cache.clear()
        _table_gen += 1
        return _table_gen


def _load(kind: Optional[str] = None) -> Dict:
    # keyed by the RESOLVED path, so redirecting DBCSR_TPU_PARAMS_DIR
    # mid-process is honored without manual cache clearing
    path = params_path(kind or device_kind())
    with _lock:
        if path not in _cache:
            table = {}
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        for e in json.load(f):
                            table[_key(e["m"], e["n"], e["k"], e["dtype"],
                                       e.get("stack_size", 0))] = e
                except (OSError, ValueError, KeyError):
                    table = {}
            _cache[path] = table
        return _cache[path]


def lookup(m: int, n: int, k: int, dtype,
           stack_size: Optional[int] = None) -> Optional[Dict]:
    """Tuned entry for this (m, n, k, dtype) on the current device.

    With ``stack_size``, the same-shape row tuned nearest that size (in
    log space, larger-S winning ties) is returned; without it, the
    largest-S row (production scale)."""
    import math

    import numpy as np

    try:
        path = params_path()
        table = _load()
    except Exception:
        return None
    rows = _by_shape(path, table).get((m, n, k, np.dtype(dtype).name), [])
    if not rows:
        return None
    if stack_size is None:
        return max(rows, key=lambda e: e.get("stack_size", 0))
    want = math.log(max(int(stack_size), 1))
    return min(
        rows,
        key=lambda e: (
            abs(math.log(max(e.get("stack_size", 1), 1)) - want),
            -e.get("stack_size", 0),
        ),
    )


# a donor entry only predicts for shapes within this flop-count ratio;
# farther shapes get no opinion (the default dispatch heuristics apply)
_PREDICT_MAX_FLOP_RATIO = 16.0

_predict_cache: Dict[tuple, Optional[Dict]] = {}


def predict(m: int, n: int, k: int, dtype,
            stack_size: Optional[int] = None) -> Optional[Dict]:
    """Nearest-tuned-entry prediction for an UNTUNED (m, n, k).

    The analog of the reference's predictive-modeling pipeline
    (`src/acc/libsmm_acc/predict/` — a trained model covers triplets the
    autotuner never ran): here the tuned table is small and the launch
    space is {driver, grouping}, so nearest-neighbor in log-flops space
    within the same dtype — capped at a 16x flop-count ratio, so a lone
    distant donor can't dictate dispatch globally — is a sound
    estimator; among equally-near shapes the row tuned nearest the live
    stack size wins.  Results are memoized (this sits on the multiply
    hot path).  Returns a copy of the donor entry tagged
    "predicted_from"."""
    import numpy as np

    exact = lookup(m, n, k, dtype, stack_size)
    if exact is not None:
        return exact
    # keyed by the resolved params file so env-redirected tables (tests,
    # DBCSR_TPU_PARAMS_DIR) never serve stale predictions.  Exact S in
    # the key: the engine buckets stack lengths already, so distinct S
    # values stay few — and a bucketed key would make the nearest-S
    # donor choice depend on which S in the bucket was queried first
    ck = (params_path(), m, n, k, np.dtype(dtype).name,
          None if stack_size is None else int(stack_size))
    if ck in _predict_cache:
        return _predict_cache[ck]
    gen0 = _table_gen
    try:
        table = _load()
    except Exception:
        return None
    want_dtype = np.dtype(dtype).name
    best, best_d = None, None
    target = np.log(float(m) * n * k)
    want_s = None if stack_size is None else np.log(float(max(stack_size, 1)))
    max_d = np.log(_PREDICT_MAX_FLOP_RATIO)
    for e in table.values():
        if e["dtype"] != want_dtype:
            continue
        d = abs(np.log(float(e["m"]) * e["n"] * e["k"]) - target)
        if d > max_d:
            continue
        if want_s is None:
            ds = -float(e.get("stack_size", 0))  # larger S preferred
        else:
            ds = abs(np.log(float(max(e.get("stack_size", 1), 1))) - want_s)
        key = (d, ds)
        if best_d is None or key < best_d:
            best, best_d = e, key
    out = None
    if best is not None:
        # an exact-shape row returned above, so every pool row is a
        # donor; the tag gates bf16-crosspack/pack acceptance on
        # exactness
        out = dict(best, predicted_from=(best["m"], best["n"], best["k"]))
    with _lock:
        if _table_gen == gen0:  # table unchanged while we computed
            _predict_cache[ck] = out
    return out


def save_entry(entry: Dict, kind: Optional[str] = None) -> str:
    """Merge one tuned entry into the device's parameter file."""
    kind = kind or device_kind()
    table = _load(kind)
    with _lock:
        table[_key(entry["m"], entry["n"], entry["k"], entry["dtype"],
                   entry.get("stack_size", 0))] = entry
        os.makedirs(_params_dir(), exist_ok=True)
        path = params_path(kind)
        with open(path, "w") as f:
            json.dump(sorted(table.values(), key=lambda e: (e["m"], e["n"], e["k"])),
                      f, indent=1)
        # after the insert, under the lock: a concurrent predict() must
        # not be able to re-memoize a pre-insert prediction (the bumped
        # generation invalidates any in-flight computation)
        global _table_gen
        _table_gen += 1
        _predict_cache.clear()
    return path


def delete_entry(m: int, n: int, k: int, dtype, stack_size,
                 kind: Optional[str] = None) -> bool:
    """Remove one row from the device's parameter file (the tune
    store's demotion path — `save_entry`'s mirror).  Returns whether a
    row was actually removed; the generation bumps either way only on a
    real removal."""
    kind = kind or device_kind()
    table = _load(kind)
    key = _key(m, n, k, dtype, stack_size)
    with _lock:
        if key not in table:
            return False
        del table[key]
        os.makedirs(_params_dir(), exist_ok=True)
        path = params_path(kind)
        with open(path, "w") as f:
            json.dump(sorted(table.values(),
                             key=lambda e: (e["m"], e["n"], e["k"])),
                      f, indent=1)
        global _table_gen
        _table_gen += 1
        _predict_cache.clear()
    return True
