"""Mesh construction for the 2.5D process grid.

Axes: ('kl', 'pr', 'pc') — kl = 3D k-layers (ref NUM_LAYERS_3D /
`dbcsr_mm_3d.F:983-1134`), pr x pc = the Cannon grid (ref
`dbcsr_mp_type`, `dbcsr_types.F:110-134`).

Shape policy (`grid_shape`): square pr == pc grids run the skewed
sparse Cannon; when the device count has no usable square factor (6,
10, 14, ...) or an explicit layer count forces it (8 devices, layers=1),
the grid goes RECTANGULAR pr != pc and the sparse engine switches to
the all-gather algorithm (`sparse_dist._stack_run_mesh(gather=True)`) — the
role the reference gives to image distributions over arbitrary
nprows x npcols grids (`dbcsr_types.F:188-223`,
`dbcsr_mm_dist_operations.F:58`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def _balanced_factor(q: int) -> Tuple[int, int]:
    """(pr, pc) with pr * pc == q, pr <= pc, as close to square as
    possible (pr = largest divisor <= sqrt(q))."""
    pr = 1
    for d in range(int(np.sqrt(q)), 0, -1):
        if q % d == 0:
            pr = d
            break
    return pr, q // pr


def grid_shape(n_devices: int, layers: Optional[int] = None) -> Tuple[int, int, int]:
    """Pick (kl, pr, pc) with kl * pr * pc == n_devices.

    Preference order: the largest SQUARE pr == pc grid (fewest layers;
    runs the skewed Cannon), else a rectangular balanced pr x pc (runs
    the all-gather engine).  ``layers=None`` consults the NUM_LAYERS_3D
    analog (`config.num_layers_3d`, ref `dbcsr_config.F:152`) before
    auto-choosing; an explicit layer count is honored exactly, going
    rectangular when n/layers is not a perfect square."""
    if layers is None:
        from dbcsr_tpu.core.config import get_config

        cfg_layers = get_config().num_layers_3d
        if cfg_layers >= 1:
            layers = cfg_layers
    if layers is not None:
        q, rem = divmod(n_devices, layers)
        if rem:
            raise ValueError(
                f"{n_devices} devices not divisible by {layers} layers"
            )
        s = int(round(np.sqrt(q)))
        if s * s == q:
            return layers, s, s
        pr, pc = _balanced_factor(q)
        return layers, pr, pc
    for s in range(int(np.sqrt(n_devices)), 1, -1):
        if n_devices % (s * s) == 0:
            return n_devices // (s * s), s, s
    pr, pc = _balanced_factor(n_devices)
    return 1, pr, pc


def make_grid(
    n_devices: Optional[int] = None,
    devices=None,
    layers: Optional[int] = None,
) -> Mesh:
    """Build the ('kl','pr','pc') mesh (ref `mp_cart_create`)."""
    if devices is None:
        devices = jax.devices()[: (n_devices or len(jax.devices()))]
    n = len(devices)
    if n_devices is not None and n < n_devices:
        raise ValueError(f"requested {n_devices} devices, have {n}")
    kl, pr, pc = grid_shape(n, layers)
    arr = np.asarray(devices).reshape(kl, pr, pc)
    return Mesh(arr, axis_names=("kl", "pr", "pc"))


def optimize_grid(mesh: Mesh, nsplit: int, long_dim: str) -> Mesh:
    """Re-factor the SAME devices into the ('kl','pr','pc') shape that
    best fits a batch of contractions — the mesh analog of the
    reference's batched pgrid re-optimization
    (`dbcsr_tensor.F:1964-2186` re-chooses process-grid dims between
    tensor batches).

    m/n-long (grouped TAS) batches want the group axis as large as the
    computed nsplit can fill: kl positions beyond nsplit would idle, so
    pick the largest kl <= nsplit (the always-offered kl=1 rectangular
    candidate guarantees a match).  k-long batches run
    2.5D k-layers, whose replication optimum scales like n^(1/3)
    (communication-avoiding Cannon): pick kl nearest that.
    Returns the input mesh unchanged when it already matches.
    """
    devs = list(mesh.devices.flat)
    n = len(devs)
    cands = [
        (n // (s * s), s, s)
        for s in range(1, int(round(n ** 0.5)) + 1)
        if n % (s * s) == 0
    ]
    # always offer the balanced rectangular single-layer grid
    # (all-gather engine): it keeps C partitioned where kl-heavy shapes
    # replicate it through the psum, and keeps all devices busy when no
    # square factorization fits the nsplit demand
    pr, pc = _balanced_factor(n)
    if (1, pr, pc) not in cands:
        cands.append((1, pr, pc))
    if long_dim in ("m", "n"):
        # the kl=1 rectangular candidate always qualifies, so `ok` is
        # never empty
        ok = [c for c in cands if c[0] <= max(int(nsplit), 1)]
        kl, pr, pc = max(ok)
    else:
        target = max(int(round(n ** (1.0 / 3.0))), 1)
        kl, pr, pc = min(cands, key=lambda c: (abs(c[0] - target), -c[1]))
    if (kl, pr, pc) == (mesh.shape["kl"], mesh.shape["pr"], mesh.shape["pc"]):
        return mesh
    return Mesh(np.asarray(devs).reshape(kl, pr, pc),
                axis_names=("kl", "pr", "pc"))
