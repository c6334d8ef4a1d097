"""Upper layers on the fused, device-resident hot path: tensor
contractions join the correlated ops plane (product_id on the event
bus), route through the fused superstack planner, and the TAS split
loop runs as a chained workload whose per-split restage collapses —
plus the committed tier-2.10 contraction A/B evidence."""

import itertools
import json
import os
import sys

import numpy as np
import pytest

from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.obs import events as obs_events
from dbcsr_tpu.obs import flight, metrics
from dbcsr_tpu.parallel import make_grid
from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans
from dbcsr_tpu.tensor import create_tensor
from dbcsr_tpu.tensor.contract import contract

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand_tensor(name, blk_sizes, occ, seed=0):
    rng = np.random.default_rng(seed)
    t = create_tensor(name, blk_sizes)
    for idx in itertools.product(*(range(len(n)) for n in blk_sizes)):
        if rng.random() < occ:
            t.put_block(idx, rng.standard_normal(t.block_shape(idx)))
    return t.finalize()


def _contract_3c(a3, m2, c3, **kw):
    """T(i,j,k) M(k,l) -> C(i,j,l), the 3-center-integral pattern."""
    return contract(1.0, a3, m2, 0.0, c3,
                    contract_a=(2,), notcontract_a=(0, 1),
                    contract_b=(0,), notcontract_b=(1,),
                    map_1=(0, 1), map_2=(2,), **kw)


@pytest.fixture(autouse=True)
def _restore_knob():
    prev = get_config().cannon_overlap
    yield
    set_config(cannon_overlap=prev)


def test_contract_product_on_event_bus():
    """tensor.contract is a first-class product on the ops plane: one
    correlation scope wraps the reshape->multiply->map pipeline, so
    its begin/end events carry a product_id exactly like mesh/TAS
    multiplies have since the double-buffer PR."""
    obs_events.set_enabled(True)
    obs_events.clear()
    si, sj, sk, sl = [3, 2], [2, 3], [3, 3], [2, 2]
    a3 = _rand_tensor("a3", [si, sj, sk], occ=0.8, seed=3)
    m2 = _rand_tensor("m2", [sk, sl], occ=0.9, seed=4)
    c3 = create_tensor("c3", [si, sj, sl])
    c3.finalize()
    _contract_3c(a3, m2, c3)
    begins = [e for e in obs_events.records(kind="multiply_begin")
              if e.get("op") == "tensor_contract"]
    assert begins and begins[-1]["product_id"]
    pid = begins[-1]["product_id"]
    rec = [r for r in flight.records() if r.get("op") == "tensor_contract"]
    assert rec and rec[-1]["product_id"] == pid
    # the inner TAS/2D multiplies correlate as their own products —
    # the bus never shows anonymous work under the contraction
    for e in obs_events.records(kind="multiply_begin"):
        assert e.get("product_id")


def test_contract_routes_fused_planner():
    """A contraction workload whose contracted dimension mixes block
    sizes puts several (abin, bbin) span families in each C bin — the
    inner multiplies must lower through the fused superstack planner
    (dbcsr_tpu_dispatches_total{mode=fused} increments), not per-span
    dispatches."""
    si, sj, sk, sl = [4, 3], [3, 4], [4, 5, 4, 5], [3, 4]
    # under the occupancy at which the planner takes both operands dense
    # (one chip runs a contraction as one multiply: no split forces the
    # stack engine)
    a3 = _rand_tensor("a3", [si, sj, sk], occ=0.6, seed=3)
    m2 = _rand_tensor("m2", [sk, sl], occ=0.6, seed=4)
    metrics.reset()
    c3 = create_tensor("c3", [si, sj, sl])
    c3.finalize()
    _contract_3c(a3, m2, c3)
    disp = metrics.counter_items("dbcsr_tpu_dispatches_total")
    fused = sum(v for lab, v in disp if lab.get("mode") == "fused")
    assert fused > 0, disp
    want = np.einsum("ijk,kl->ijl", a3.to_dense(), m2.to_dense())
    np.testing.assert_allclose(c3.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_pipeline_bitwise_rect_mesh():
    """contract() over a rectangular grid rides the chunked all-gather
    pipeline; serial and pipelined execution must be bitwise
    identical (the tensor-layer view of the gather_pipe contract)."""
    bs = [4] * 5
    a3 = _rand_tensor("a3", [bs, bs, bs], occ=0.5, seed=7)
    m2 = _rand_tensor("m2", [bs, bs], occ=0.8, seed=8)
    mesh = make_grid(6, layers=1)  # (1, 2, 3)
    outs = {}
    for mode in ("serial", "double_buffer"):
        set_config(cannon_overlap=mode)
        clear_mesh_plans()
        c3 = create_tensor("c3", [bs, bs, bs])
        c3.finalize()
        _contract_3c(a3, m2, c3, mesh=mesh)
        outs[mode] = np.asarray(c3.to_dense())
    assert (outs["serial"] == outs["double_buffer"]).all()
    # the contraction's own scope commits last; the inner distributed
    # multiply's record carries the pipeline decision
    rec = [r for r in flight.records() if r.get("op") == "mesh_multiply"][-1]
    assert rec["cannon_mode"] == "double_buffer"


def test_tas_chain_restage_collapse():
    """The TAS split loop is a chained workload now: with device
    residency on, per-split H2D collapses to ~zero after the first
    iteration, while the unchained control keeps restaging every
    iteration — bitwise identical results.  The device-side driver is
    forced (the CPU-tuned host driver's per-multiply C round-trips
    are algorithmic, not restage overhead)."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.mm import multiply as mm_multiply
    from dbcsr_tpu.tas import tas_multiply

    prev_driver = get_config().mm_driver
    prev_format = get_config().mm_format
    set_config(mm_format="stack", mm_driver="xla")
    try:
        per_iter = {}
        dense = {}
        for pooled in (True, False):
            mempool.set_enabled(pooled)
            mempool.clear()
            mempool.reset_stats()
            mm_multiply._plan_cache.clear()
            rng = np.random.default_rng(7)
            ls, ss = [5, 4] * 8, [5, 4, 5]
            a = dt.make_random_matrix("a", ls, ss, occupation=0.6, rng=rng)
            b = dt.make_random_matrix("b", ss, ss, occupation=0.8, rng=rng)
            rows = []
            for _ in range(3):
                c = dt.create("c", ls, ss)
                tr0 = mempool.transfer_totals()
                tas_multiply("N", "N", 1.0, a, b, 0.0, c, nsplit=4)
                tr1 = mempool.transfer_totals()
                rows.append(int((tr1["h2d"] - tr0["h2d"])
                                + (tr1["d2h"] - tr0["d2h"])))
            per_iter[pooled] = rows
            dense[pooled] = np.asarray(dt.to_dense(c))
    finally:
        mempool.set_enabled(True)
        set_config(mm_format=prev_format, mm_driver=prev_driver)
    assert (dense[True] == dense[False]).all()
    # chained: steady state moves (almost) nothing; unchained: every
    # iteration pays the same per-split staging again
    assert max(per_iter[True][1:]) < per_iter[False][-1], per_iter
    assert max(per_iter[True][1:]) <= per_iter[True][0] // 4, per_iter
    assert min(per_iter[False]) > 0, per_iter


# -------------------------------------------- committed A/B evidence

def test_contract_bench_smoke(tmp_path):
    """The A/B tool runs end to end on a small case: exit 0, all four
    legs present, bitwise identical within each pair."""
    import subprocess

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the tool forces its own 6-device world
    env.pop("DBCSR_TPU_SYNC_TIMING", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "contract_bench.py"),
         "--nblk", "4", "--nrep", "1", "--iters", "2", "--tall", "4"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["checksum_bitwise_match"] is True
    assert set(row["ab"]) == {"serial", "pipelined", "unchained", "chained"}
    for leg in ("serial", "pipelined"):
        assert 0.0 <= row["ab"][leg]["exposed_fraction"] <= 1.0
    assert row["cannon_mode"] == "double_buffer"
