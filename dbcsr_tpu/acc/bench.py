"""Standalone acc-layer micro-benchmarks.

Analog of `src/acc/acc_bench_smm.c` / `acc_bench_trans.c` (~1,000 LoC C
drivers, `src/acc/README.md:31-43`): exercise ONLY the acc contract —
`process_stack` / `transpose_blocks` / `block_norms` — with no engine
or index machinery, validating against a host (NumPy) checksum exactly
like `libsmm_acc_benchmark.cpp:60-85`, and reporting GFLOP/s and GB/s.

CLI (positional, `0` = default, mirroring the reference drivers):

    python -m dbcsr_tpu.acc.bench smm   [nrep] [stack_size] [m] [n] [k] [dtype]
    python -m dbcsr_tpu.acc.bench trans [nrep] [stack_size] [m] [n] [dtype]

dtype is the reference datatype enum (1=r4, 3=r8; `acc_libsmm.h:31-36`).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from dbcsr_tpu.core.kinds import dtype_of
from dbcsr_tpu.utils.sync import fetch_fence


def _rand_stack(rng, nblocks_a, nblocks_b, nblocks_c, stack_size):
    ai = rng.integers(0, nblocks_a, stack_size).astype(np.int32)
    bi = rng.integers(0, nblocks_b, stack_size).astype(np.int32)
    ci = np.sort(rng.integers(0, nblocks_c, stack_size)).astype(np.int32)
    return ai, bi, ci


def bench_smm(nrep=5, stack_size=30000, m=23, n=23, k=23, dtype_enum=3,
              out=print, seed=42):
    """Batched-SMM benchmark + host validation.  Returns a result dict."""
    import jax
    import jax.numpy as jnp

    dtype = dtype_of(dtype_enum)
    rng = np.random.default_rng(seed)
    # reference sizing: ~stack_size/16 distinct blocks cycle through HBM
    na = nb = max(stack_size // 16, 1)
    nc = max(stack_size // 8, 1)
    a_host = rng.standard_normal((na, m, k)).astype(dtype)
    b_host = rng.standard_normal((nb, k, n)).astype(dtype)
    ai, bi, ci = _rand_stack(rng, na, nb, nc, stack_size)
    a = jnp.asarray(a_host)
    b = jnp.asarray(b_host)

    # host oracle (float64 accumulate, like the LIBXSMM-side validation)
    want = np.zeros((nc, m, n), np.float64)
    np.add.at(
        want, ci,
        np.einsum("sij,sjk->sik", a_host[ai].astype(np.float64),
                  b_host[bi].astype(np.float64)),
    )

    from dbcsr_tpu.acc.smm import execute_stack, prepare_stack

    plan = prepare_stack(jnp.zeros((nc, m, n), dtype), a, b, ai, bi, ci)
    c = execute_stack(jnp.zeros((nc, m, n), dtype), a, b, plan, 1.0)
    # compare ON DEVICE and fetch 8 bytes, not the full result
    scale = max(np.abs(want).max(), 1.0)
    cmp_dtype = (jnp.float32 if np.dtype(dtype).itemsize <= 4
                 and not jax.config.jax_enable_x64 else jnp.float64)
    max_err = float(
        jnp.max(jnp.abs(c.astype(cmp_dtype) - jnp.asarray(want, cmp_dtype)))
    ) / scale
    # bf16 stores C at ~8 bit mantissa: even exact f32 accumulation
    # rounds to ~4e-3 relative on store, so 1e-3 would always "fail"
    itemsize = np.dtype(dtype).itemsize
    tol = 2e-2 if itemsize <= 2 else (1e-3 if itemsize <= 4 else 1e-10)
    ok = max_err < tol

    times = []
    for _ in range(nrep):
        c = jnp.zeros((nc, m, n), dtype)
        t0 = time.perf_counter()
        c = execute_stack(c, a, b, plan, 1.0)
        fetch_fence(c)  # forced completion
        times.append(time.perf_counter() - t0)
    best = min(times)
    flops = 2.0 * m * n * k * stack_size
    # HBM traffic model: gather A+B per entry, C blocks r/w once each
    # (the shared obs/costmodel convention, so kernel GB/s lines and
    # the engine's roofline rollups are directly comparable)
    from dbcsr_tpu.obs import costmodel

    bytes_moved = costmodel.stack_bytes(
        m, n, k, stack_size, nseg=nc, itemsize=np.dtype(dtype).itemsize
    )
    result = {
        "kernel": f"{m}x{n}x{k}",
        "dtype": np.dtype(dtype).name,
        "stack_size": stack_size,
        "device": str(jax.devices()[0]),
        "device_kind": str(jax.devices()[0].device_kind),
        "gflops": flops / best / 1e9,
        "gbs": bytes_moved / best / 1e9,
        "ms": best * 1e3,
        "max_rel_err": float(max_err),
        "errors": 0 if ok else 1,
        # which driver auto-dispatch chose — artifact lines are useless
        # for tuning decisions without it.  "timed": what the rep loop
        # measures — "execute" = kernel launches on a prepared stack
        # (the reference acc_bench_smm discipline); older artifact
        # lines without the field timed prepare+execute per rep
        "timed": "execute",
        "driver": plan.driver,
        "variant": ("kmerge" if plan.kmerge
                    else ("crosspack_vmem" if plan.cross_vmem
                          else ("crosspack" if plan.pack else None))),
        "r_grp": plan.r_grp,
        # xla_group: [width, groups] of every class the plan opened
        "group_classes": [list(c) for c in plan.group_classes],
        "pack": list(plan.pack) if plan.pack else None,
    }
    out(f"typename (id={dtype_enum}): {result['dtype']}")
    out(f"device: {result['device']}")
    out(f"smm {m}x{n}x{k} stack {stack_size}: {result['ms']:.2f} ms "
        f"{result['gflops']:.1f} GFLOP/s {result['gbs']:.1f} GB/s")
    out(f"errors: {result['errors']}")
    return result


def bench_trans(nrep=5, stack_size=30000, m=23, n=23, dtype_enum=3,
                out=print, seed=42):
    """Batched block-transpose benchmark (ref `acc_bench_trans.c`)."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc.smm import transpose_blocks

    dtype = dtype_of(dtype_enum)
    rng = np.random.default_rng(seed)
    nblocks = max(stack_size // 4, 1)
    host = rng.standard_normal((nblocks, m, n)).astype(dtype)
    data = jnp.asarray(host)
    got = np.asarray(transpose_blocks(data))
    ok = np.array_equal(got, host.transpose(0, 2, 1))

    times = []
    for _ in range(nrep):
        t0 = time.perf_counter()
        fetch_fence(transpose_blocks(data))  # forced completion
        times.append(time.perf_counter() - t0)
    best = min(times)
    bytes_moved = 2 * host.nbytes
    result = {
        "kernel": f"{m}x{n}",
        "dtype": np.dtype(dtype).name,
        "nblocks": nblocks,
        "device": str(jax.devices()[0]),
        "gbs": bytes_moved / best / 1e9,
        "ms": best * 1e3,
        "errors": 0 if ok else 1,
    }
    out(f"typename (id={dtype_enum}): {result['dtype']}")
    out(f"device: {result['ms']:.2f} ms {result['gbs']:.1f} GB/s")
    out(f"errors: {result['errors']}")
    return result


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("smm", "trans"):
        print(__doc__)
        return 1
    mode = argv.pop(0)
    defaults = [5, 30000, 23, 23, 23, 3] if mode == "smm" else [5, 30000, 23, 23, 3]
    vals = list(defaults)
    for i, arg in enumerate(argv[: len(defaults)]):
        if int(arg) != 0:
            vals[i] = int(arg)
    res = bench_smm(*vals) if mode == "smm" else bench_trans(*vals)
    return res["errors"]


if __name__ == "__main__":
    sys.exit(main())
