"""REAL multi-process world tests: two OS processes join via
`jax.distributed` (Gloo/TCP on the CPU backend, 4 virtual devices
each), form the ('kl','pr','pc') mesh across the world, and run (a) a
cross-process psum and (b) the flagship block-sparse Cannon — the
multi-host analog of the reference's mpiexec-spawned CTest programs
(SURVEY §4: "every test is an MPI program").
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # real 2-process world: full-suite runs only

_WORKER = r'''
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
port, pid = sys.argv[1], int(sys.argv[2])
from dbcsr_tpu.parallel import multihost
ok = multihost.init_multihost(f"localhost:{{port}}", 2, pid)
assert ok and multihost.process_count() == 2
assert multihost.process_id() == pid
mesh = multihost.make_multihost_grid()
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def body(x):
    return jax.lax.psum(x, ("kl", "pr", "pc"))

fn = jax.shard_map(body, mesh=mesh, in_specs=P(("kl", "pr", "pc")),
                   out_specs=P(("kl", "pr", "pc")))
n = int(np.prod(list(mesh.shape.values())))
out = fn(jnp.ones((n,)))
local = np.asarray(out.addressable_shards[0].data)
assert local[0] == float(n), local

from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense, checksum
from dbcsr_tpu.parallel.sparse_dist import sparse_multiply_distributed
rng = np.random.default_rng(9)
sizes = [3] * 8
a = make_random_matrix("A", sizes, sizes, occupation=0.5, rng=rng)
b = make_random_matrix("B", sizes, sizes, occupation=0.5, rng=rng)
c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)
err = np.abs(to_dense(c) - to_dense(a) @ to_dense(b)).max()
assert err < 1e-12, err

# rank-aggregated timing report: printed by rank 0 only, every rank
# participates in the allgather (ref dbcsr_timings_report.F:51-301)
from dbcsr_tpu.core import timings

lines = []
timings.report(out=lines.append, aggregate=True)
if pid == 0:
    text = "\n".join(lines)
    assert "2 ranks" in text and "SELF avg" in text, text
    assert "sparse_cannon" in text, text
else:
    assert not lines

print(f"WORKER{{pid}} OK psum={{local[0]}} err={{err:.2e}} "
      f"checksum={{checksum(c)!r}}")
multihost.shutdown_multihost()
'''


def _run_world(worker, attempt_timeout):
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_PLATFORMS", None)  # worker sets the platform itself
    # run the worker processes with the dynamic lock-order checker on:
    # the 2-process world exercises the mempool/serve/timeseries locks
    # under real concurrency (dbcsr_tpu/utils/lockcheck.py)
    env.setdefault("DBCSR_TPU_LOCKCHECK", "1")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(port), str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=attempt_timeout)[0])
    except subprocess.TimeoutExpired:
        outs = None  # port race / hung join: caller may retry
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)  # reap; close pipes
            except Exception:
                pass
    return procs, outs


def test_two_process_world_psum_and_sparse_cannon(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    # the ephemeral port can be raced between close() and the rank-0
    # bind; retry once on a hang with a fresh port
    procs, outs = _run_world(worker, attempt_timeout=120)
    if outs is None:
        procs, outs = _run_world(worker, attempt_timeout=240)
    assert outs is not None, "world never formed (twice)"
    for i, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{o[-3000:]}"
    oks = [l for o in outs for l in o.splitlines() if " OK psum=" in l]
    assert len(oks) == 2, outs
    # both ranks computed the identical checksum (cross-rank determinism,
    # the reference's dbcsr_checksum contract)
    cs = {l.split("checksum=")[1] for l in oks}
    assert len(cs) == 1, oks
