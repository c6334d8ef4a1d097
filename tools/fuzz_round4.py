"""Round-4 randomized robustness sweep: the NEW surfaces.

Random configurations over the features added this round — the
cross-packed Pallas kernel, rectangular-grid all-gather meshes,
chunked dense mode, and traffic-chosen TAS splits — each verified
against the dense NumPy oracle (the SURVEY §4 randomized-sweep
discipline used in rounds 2/3 for the base engine).

Usage: python tools/fuzz_round4.py [nconfigs] [seed]
Prints a tally; exits nonzero on any mismatch.

Large sweeps run in SUBPROCESS BATCHES of 50 configs (each batch a
fresh interpreter): every distinct random shape adds entries to XLA's
process-lifetime jit cache, and a single 300-config process was
observed to exhaust host memory (LLVM 'Cannot allocate memory').
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()


def main(nconfigs: int = 200, seed: int = 2026_0730) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm import multiply as mm
    from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed
    from dbcsr_tpu.tas import tas_multiply

    dt.init_lib()
    meshes = {
        "sq4": make_grid(4), "sq8": make_grid(8),
        "rect6": make_grid(6), "rect8": make_grid(8, layers=1),
        "rect2x3l": make_grid(6, layers=2),
    }
    rng = np.random.default_rng(seed)
    tally = {}
    failures = []
    cap0 = mm._DENSE_MAX_CANVAS
    for i in range(nconfigs):
        feature = rng.choice(["crosspack", "rect_mesh", "chunked_dense",
                              "tas_auto", "host"])
        dtype = {
            "crosspack": rng.choice([np.float32, "bf16"]),
            "rect_mesh": rng.choice([np.float64, np.float32, np.complex128]),
            "chunked_dense": np.float64,
            "tas_auto": np.float64,
            "host": rng.choice([np.float64, np.float32, np.complex128,
                                np.complex64]),
        }[feature]
        uniform = feature in ("crosspack", "chunked_dense")
        szpool = [1, 2, 3, 5, 7, 8, 13, 23]
        if uniform:
            blk = int(rng.choice([4, 7, 8, 13, 16, 23]))
            m_s = [blk] * int(rng.integers(3, 10))
            k_s = [blk] * int(rng.integers(3, 10))
            n_s = [blk] * int(rng.integers(3, 10))
        else:
            m_s = rng.choice(szpool, size=rng.integers(2, 8)).tolist()
            k_s = rng.choice(szpool, size=rng.integers(2, 8)).tolist()
            n_s = rng.choice(szpool, size=rng.integers(2, 8)).tolist()
        if feature == "tas_auto":
            # make one dimension long so splits engage
            which = rng.choice(["m", "n", "k"])
            long_sizes = [int(rng.choice([4, 8]))] * int(rng.integers(24, 48))
            if which == "m":
                m_s = long_sizes
            elif which == "n":
                n_s = long_sizes
            else:
                k_s = long_sizes
        dtj = jax.numpy.bfloat16 if dtype == "bf16" else dtype
        occ_a = float(rng.uniform(0.2, 0.9))
        occ_b = float(rng.uniform(0.2, 0.9))
        alpha = float(rng.choice([1.0, -0.5, 2.0]))
        beta = float(rng.choice([0.0, 1.0, 0.5]))
        a = dt.make_random_matrix("a", m_s, k_s, dtype=dtj, occupation=occ_a,
                                  rng=rng)
        b = dt.make_random_matrix("b", k_s, n_s, dtype=dtj, occupation=occ_b,
                                  rng=rng)
        c = dt.make_random_matrix("c", m_s, n_s, dtype=dtj,
                                  occupation=float(rng.uniform(0, 0.5)),
                                  rng=rng)
        acc_dt = (np.complex128
                  if dtype in (np.complex128, np.complex64) else np.float64)
        want = alpha * (
            dt.to_dense(a).astype(acc_dt) @ dt.to_dense(b).astype(acc_dt)
        ) + beta * dt.to_dense(c).astype(acc_dt)
        tol = 5e-2 if dtype == "bf16" else (
            5e-4 if dtype in (np.float32, np.complex64) else 1e-10)
        try:
            if feature == "crosspack":
                set_config(mm_driver="pallas_cross", validate_kernels=True)
                try:
                    dt.multiply("N", "N", alpha, a, b, beta, c)
                finally:
                    set_config(mm_driver="auto")
                got = dt.to_dense(c)
            elif feature == "rect_mesh":
                mesh = meshes[rng.choice(["rect6", "rect8", "rect2x3l",
                                          "sq4", "sq8"])]
                out = sparse_multiply_distributed(alpha, a, b, beta, c, mesh)
                got = dt.to_dense(out)
            elif feature == "chunked_dense":
                mm._DENSE_MAX_CANVAS = int(rng.choice([700, 2000, 5000]))
                set_config(mm_format="dense")
                try:
                    dt.multiply("N", "N", alpha, a, b, beta, c)
                finally:
                    set_config(mm_format="auto")
                    mm._DENSE_MAX_CANVAS = cap0
                got = dt.to_dense(c)
            elif feature == "host":
                set_config(mm_driver="host")
                try:
                    dt.multiply("N", "N", alpha, a, b, beta, c)
                finally:
                    set_config(mm_driver="auto")
                got = dt.to_dense(c)
            else:  # tas_auto
                mesh = (meshes[rng.choice(["sq8", "rect6"])]
                        if rng.random() < 0.7 else None)
                tas_multiply("N", "N", alpha, a, b, beta, c, mesh=mesh)
                got = dt.to_dense(c)
            err = np.abs(got.astype(want.dtype) - want).max() / max(
                1.0, np.abs(want).max())
            ok = err < tol
        except Exception as exc:  # noqa: BLE001 — tally and report below
            ok, err = False, f"{type(exc).__name__}: {exc}"
        key = (feature, str(np.dtype(dtj).name))
        tally[key] = tally.get(key, [0, 0])
        tally[key][0 if ok else 1] += 1
        if not ok:
            failures.append((i, feature, dtype, err))
        if (i + 1) % 25 == 0:
            print(f"  {i + 1}/{nconfigs} done, {len(failures)} failures",
                  flush=True)
    print("\ntally (feature, dtype): ok/fail")
    for key in sorted(tally):
        ok_n, bad_n = tally[key]
        print(f"  {key}: {ok_n}/{bad_n}")
    for f in failures[:20]:
        print("FAIL", f)
    print(f"\n{nconfigs} configs, {len(failures)} failures")
    return 1 if failures else 0


def main_batched(nconfigs: int, seed: int, batch: int = 50) -> int:
    """Split the sweep into fresh-interpreter batches (see module
    docstring); aggregates exit status and streams each batch's tail."""
    import subprocess

    rc = 0
    done = 0
    while done < nconfigs:
        take = min(batch, nconfigs - done)
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--batch",
                 str(take), str(seed + done)],
                capture_output=True, text=True, timeout=3600,
            )
            code, out_s, err_s = r.returncode, r.stdout or "", r.stderr or ""
        except subprocess.TimeoutExpired as exc:
            code = -1
            out_s = exc.stdout or ""
            err_s = "batch TIMEOUT after 3600 s\n" + (exc.stderr or "")
        tail = "\n".join(out_s.strip().splitlines()[-8:])
        print(f"--- batch @{done} (+{take}), rc={code} ---\n{tail}",
              flush=True)
        if code:
            rc = 1
            print("stderr tail:\n" +
                  "\n".join(err_s.strip().splitlines()[-10:]), flush=True)
        done += take
    print(f"\nbatched sweep: {nconfigs} configs, overall rc={rc}")
    return rc


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--batch":
        sys.exit(main(int(args[1]), int(args[2])))
    n = int(args[0]) if args else 200
    s = int(args[1]) if len(args) > 1 else 2026_0730
    sys.exit(main_batched(n, s) if n > 60 else main(n, s))
