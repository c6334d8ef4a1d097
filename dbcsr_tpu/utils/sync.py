"""Forced-completion fencing for honest timing.

A data-dependent fetch of one element cannot be served before the
producing program finished — the moral equivalent of the reference's
`mp_sync` timing fence (`dbcsr_performance_multiply.F:597`).  Every
timed path (perf driver, autotuner, acc micro-benchmarks, chip_smoke)
fences through this helper so the contract lives in one place.
"""

from __future__ import annotations

import numpy as np


def fetch_fence(arr) -> float:
    """Force completion of the program producing ``arr`` by
    fetching its first element (8-byte d2h); returns it as float.
    Indexed, not ``ravel()[0]``: flattening a tiled (N, 23, 23) bin is
    a relayout of the whole array — on a v5e it made fencing the f32
    north star's four C bins cost 16 ms of a 0.25 s multiply; indexed,
    7 ms (an f64 C costs 43 ms either way: ~10 ms per f64 fetch)."""
    return float(np.asarray(arr[(0,) * arr.ndim]).real)
