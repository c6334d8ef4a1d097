"""Double-buffered Cannon ticks: knob validation, bitwise identity of
the overlapped vs serial execution modes on every distributed route
(dense Cannon, sparse mesh square grid, all-gather rectangular grid,
grouped TAS), the measured-overlap plumbing
(``dbcsr_tpu_cannon_overlap_measured`` under DBCSR_TPU_SYNC_TIMING),
and the resilience contract: a fault mid-shift degrades to the serial
fused program with checksums intact, breaker-integrated."""

import os
import sys

import numpy as np
import pytest

from dbcsr_tpu.core import stats
from dbcsr_tpu.core.config import Config, get_config, set_config
from dbcsr_tpu.obs import metrics
from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix, to_dense
from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed
from dbcsr_tpu.parallel import overlap as ovl
from dbcsr_tpu.parallel.cannon import cannon_multiply_dense
from dbcsr_tpu.parallel.sparse_dist import (
    clear_mesh_plans, tas_grouped_multiply,
)
from dbcsr_tpu.resilience import breaker, faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mesh8():
    return make_grid(8)  # (kl=2, pr=2, pc=2)


@pytest.fixture
def mesh4():
    return make_grid(4)  # (1, 2, 2)


@pytest.fixture
def mesh6():
    return make_grid(6)  # (1, 2, 3): rectangular -> all-gather route


@pytest.fixture(autouse=True)
def _restore_knob():
    prev = get_config().cannon_overlap
    yield
    set_config(cannon_overlap=prev)
    breaker.reset_board()


def _rand(name, occ=0.6, bs=(3, 5, 4, 2, 6, 3), seed=3, dtype=np.float64):
    rng = np.random.default_rng(seed)
    return make_random_matrix(name, list(bs), list(bs), dtype=dtype,
                              occupation=occ, rng=rng)


def _mesh_ab(mesh, mode, a, b, c0=None, alpha=2.0, beta=0.5, **kw):
    set_config(cannon_overlap=mode)
    clear_mesh_plans()
    ci = c0.copy("Ci") if c0 is not None else None
    out = sparse_multiply_distributed(alpha, a, b, beta if ci is not None
                                      else 0.0, ci, mesh, **kw)
    return to_dense(out)


# ------------------------------------------------------------- knob

def test_knob_validation():
    with pytest.raises(ValueError, match="cannon_overlap"):
        set_config(cannon_overlap="pipelined")
    # a rejected update must leave the live config untouched
    assert get_config().cannon_overlap in ("auto", "double_buffer", "serial")
    for v in ("auto", "double_buffer", "serial"):
        cfg = Config(cannon_overlap=v)
        cfg.validate()
    with pytest.raises(ValueError):
        Config(cannon_overlap="SERIAL").validate()


def test_resolve_mode_policy():
    set_config(cannon_overlap="auto")
    assert ovl.resolve_mode("mesh", "1x2x2", 2)[0] == "double_buffer"
    assert ovl.resolve_mode("mesh", "1x1x1", 1) == ("serial",
                                                    "no-ring-shifts")
    set_config(cannon_overlap="serial")
    assert ovl.resolve_mode("mesh", "1x2x2", 2) == ("serial", "config")
    set_config(cannon_overlap="double_buffer")
    mode, why = ovl.resolve_mode("mesh", "1x2x2", 2)
    assert (mode, why) == ("double_buffer", "config")


# ------------------------------------------- bitwise identity, by route

def test_dense_cannon_bitwise_identity(mesh8):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 16))
    b = rng.standard_normal((16, 12))
    set_config(cannon_overlap="serial")
    c_ser = np.asarray(cannon_multiply_dense(mesh8, a, b))
    set_config(cannon_overlap="double_buffer")
    c_db = np.asarray(cannon_multiply_dense(mesh8, a, b))
    assert (c_ser == c_db).all()
    np.testing.assert_allclose(c_db, a @ b, rtol=1e-12)


def test_mesh_square_bitwise_identity(mesh8):
    a, b, c0 = _rand("A"), _rand("B", seed=4), _rand("C", occ=0.3, seed=5)
    ser = _mesh_ab(mesh8, "serial", a, b, c0)
    db = _mesh_ab(mesh8, "double_buffer", a, b, c0)
    assert (ser == db).all()
    ref = 2.0 * (to_dense(a) @ to_dense(b)) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(db, ref, rtol=1e-12, atol=1e-12)


def _grouped_case(layout):
    """(multiply(mode) -> dense C, the dense reference) of one layout
    the grouped stacks (`mm_driver="xla_group"`: the tiling and the
    chunk loop of `acc/smm.py`) run in."""
    import jax
    from jax.sharding import Mesh

    if layout == "tas_grouped":
        bs_tall, bs = [4] * 12, [4] * 5
        rng = np.random.default_rng(27)
        a = make_random_matrix("AT", bs_tall, bs, occupation=0.5, rng=rng)
        b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
        mesh = make_grid(8)

        def multiply(mode):
            set_config(cannon_overlap=mode)
            clear_mesh_plans()
            return to_dense(tas_grouped_multiply(2.0, a, b, 0.0, None, mesh))
    else:
        dtype = np.complex128 if layout == "cannon_2x2_c128" else np.float64
        a = _rand("A", seed=11, dtype=dtype)
        b = _rand("B", seed=12, dtype=dtype)
        mesh = {
            "cannon_2x2": lambda: make_grid(4),
            "cannon_2x2_c128": lambda: make_grid(4),
            # rectangular: the chunked all-gather, whose pad ids name
            # rows of the concatenated operands
            "allgather_2x3": lambda: make_grid(6),
            # a LAYERED rectangular grid (kl=2, 1x2): the psum tail
            "allgather_layered_1x2": lambda: Mesh(
                np.asarray(jax.devices()[:4]).reshape(2, 1, 2),
                axis_names=("kl", "pr", "pc")),
        }[layout]()

        def multiply(mode):
            return _mesh_ab(mesh, mode, a, b)
    return multiply, 2.0 * (to_dense(a) @ to_dense(b))


@pytest.mark.parametrize("layout", [
    "cannon_2x2", "cannon_2x2_c128", "allgather_2x3",
    "allgather_layered_1x2", "tas_grouped"])
def test_grouped_stacks_bitwise_identity_and_dense_reference(layout):
    # the grouped (xla_group) stacks through the split per-tick
    # programs and the fused one: one body (`_tick_contrib_chunked` ->
    # `acc/smm.py:group_chunk_loop`), so bit for bit the same C, and
    # the dense product
    prev = get_config().mm_driver
    set_config(mm_driver="xla_group")
    try:
        multiply, ref = _grouped_case(layout)
        ser = multiply("serial")
        db = multiply("double_buffer")
    finally:
        set_config(mm_driver=prev)
    assert (ser == db).all()
    np.testing.assert_allclose(db, ref, rtol=1e-12, atol=1e-12)


def test_mesh_allgather_route_identity(mesh6):
    # rectangular grid: the chunked-gather pipeline (per-source-shard
    # ring steps overlapping the stack chunks) vs the fused
    # one-collective program — bitwise identical, decision recorded
    from dbcsr_tpu.obs import flight

    a, b = _rand("A"), _rand("B", seed=4)
    ser = _mesh_ab(mesh6, "serial", a, b)
    db = _mesh_ab(mesh6, "double_buffer", a, b)
    assert (ser == db).all()
    rec = flight.records()[-1]
    assert rec["op"] == "mesh_multiply"
    assert rec["cannon_mode"] == "double_buffer"


def test_mesh_allgather_beta_filtered_identity(mesh6):
    # the gather pipeline through the windowed-beta and filtered legs:
    # beta != 0 merges old C through the shared finish program, and
    # filtered products (plan rebuilt every multiply) still pipeline
    a, b, c0 = _rand("A"), _rand("B", seed=4), _rand("C", occ=0.3, seed=5)
    ser = _mesh_ab(mesh6, "serial", a, b, c0)
    db = _mesh_ab(mesh6, "double_buffer", a, b, c0)
    assert (ser == db).all()
    ser_f = _mesh_ab(mesh6, "serial", a, b, filter_eps=1e-3)
    db_f = _mesh_ab(mesh6, "double_buffer", a, b, filter_eps=1e-3)
    assert (ser_f == db_f).all()


def test_tas_route_identity(mesh8):
    from dbcsr_tpu.obs import flight

    bs_tall, bs = [4] * 12, [4] * 5
    rng = np.random.default_rng(7)
    at = make_random_matrix("AT", bs_tall, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
    outs = {}
    for mode in ("serial", "double_buffer"):
        set_config(cannon_overlap=mode)
        clear_mesh_plans()
        outs[mode] = to_dense(tas_grouped_multiply(1.0, at, b, 0.0, None,
                                                   mesh8))
    assert (outs["serial"] == outs["double_buffer"]).all()
    rec = flight.records()[-1]
    assert rec["op"] == "tas_mesh_multiply"
    # the grouped metronome staggers through the double-buffer driver
    # now: the pipelined decision must be what actually ran
    assert rec["cannon_mode"] == "double_buffer"


def test_tas_route_beta_filtered_identity(mesh8):
    # grouped-TAS pipeline through beta accumulation (cinit assembled
    # into the group panels, merged by the shared finish tail) and a
    # filtered product (plan rebuilt per multiply)
    bs_tall, bs = [4] * 12, [4] * 5
    rng = np.random.default_rng(17)
    at = make_random_matrix("AT", bs_tall, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
    c0 = make_random_matrix("C0", bs_tall, bs, occupation=0.3, rng=rng)
    outs, outs_f = {}, {}
    for mode in ("serial", "double_buffer"):
        set_config(cannon_overlap=mode)
        clear_mesh_plans()
        ci = c0.copy("Ci")
        outs[mode] = to_dense(tas_grouped_multiply(2.0, at, b, 0.5, ci,
                                                   mesh8))
        clear_mesh_plans()
        outs_f[mode] = to_dense(tas_grouped_multiply(
            1.0, at, b, 0.0, None, mesh8, filter_eps=1e-3))
    assert (outs["serial"] == outs["double_buffer"]).all()
    assert (outs_f["serial"] == outs_f["double_buffer"]).all()
    ref = 2.0 * (to_dense(at) @ to_dense(b)) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(np.asarray(outs["double_buffer"]), ref,
                               rtol=1e-12, atol=1e-12)


def test_filtered_product_identity(mesh4):
    # filtered products bypass the plan cache but not the tick driver
    a, b = _rand("A", seed=21), _rand("B", seed=22)
    ser = _mesh_ab(mesh4, "serial", a, b, filter_eps=1e-3)
    db = _mesh_ab(mesh4, "double_buffer", a, b, filter_eps=1e-3)
    assert (ser == db).all()


# --------------------------------------------------- measured plumbing

def test_measured_overlap_plumbing(mesh4, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    metrics.reset()
    a, b = _rand("A"), _rand("B", seed=4)
    db = _mesh_ab(mesh4, "double_buffer", a, b)
    ser = _mesh_ab(mesh4, "serial", a, b)
    assert (ser == db).all()  # the measured paths stay bitwise identical
    g = metrics.gauge(ovl.MEASURED_GAUGE)
    for mode in ("double_buffer", "serial"):
        v = g.value(engine="mesh", grid="1x2x2", mode=mode)
        assert 0.0 <= v <= 1.0, (mode, v)
    roll = stats.cannon_overlap_rollup()["mesh"]["1x2x2"]
    assert roll["shift_exposed_s"] >= 0 and roll["compute_s"] > 0
    assert 0.0 <= roll["measured_exposed"] <= 1.0
    # rolled into the roofline next to the modeled ratio
    snap = metrics.snapshot()
    cell = snap["roofline"]["mesh"]["cannon_overlap"]["1x2x2"]
    assert "measured_exposed" in cell and "modeled_ratio" in cell


def test_measured_dense_engine(mesh8, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    metrics.reset()
    rng = np.random.default_rng(1)
    a = rng.standard_normal((8, 16))
    b = rng.standard_normal((16, 12))
    set_config(cannon_overlap="double_buffer")
    cannon_multiply_dense(mesh8, a, b)
    v = metrics.gauge(ovl.MEASURED_GAUGE).value(
        engine="dense", grid="2x2x2", mode="double_buffer")
    assert 0.0 <= v <= 1.0
    assert stats.cannon_overlap_rollup()["dense"]["2x2x2"]["compute_s"] > 0


def test_modeled_gauges_labeled_by_engine(mesh4, mesh8):
    metrics.reset()
    a, b = _rand("A"), _rand("B", seed=4)
    set_config(cannon_overlap="serial")
    clear_mesh_plans()
    sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4)
    rng = np.random.default_rng(1)
    cannon_multiply_dense(mesh8, rng.standard_normal((8, 16)),
                          rng.standard_normal((16, 12)))
    g = metrics.gauge("dbcsr_tpu_cannon_overlap_ratio")
    assert g.value(engine="mesh", grid="1x2x2") > 0
    assert g.value(engine="dense", grid="2x2x2") > 0
    comm = metrics.gauge("dbcsr_tpu_cannon_tick_comm_bytes")
    assert comm.value(engine="mesh", grid="1x2x2") > 0


# ----------------------------------------------- resilience / chaos

def test_mesh_shift_fault_degrades_to_serial(mesh4):
    from dbcsr_tpu.obs import flight

    a, b = _rand("A"), _rand("B", seed=4)
    clean = _mesh_ab(mesh4, "double_buffer", a, b, alpha=1.0)
    # nan seed 97 lands in a panel slot tick 1 actually gathers (a
    # dead-slot seed corrupts nothing and legitimately needs no
    # degrade); the raise/oom kinds fire at the dispatch edge itself
    for schedule in ("mesh_shift:raise,times=1",
                     "mesh_shift:nan,seed=97,times=1",
                     "mesh_shift:oom,times=1"):
        breaker.reset_board()
        clear_mesh_plans()
        with faults.inject_faults(schedule) as installed:
            set_config(cannon_overlap="double_buffer")
            out = to_dense(sparse_multiply_distributed(
                1.0, a, b, 0.0, None, mesh4))
        assert sum(s.fired for s in installed) == 1, schedule
        assert (np.asarray(out) == np.asarray(clean)).all(), schedule
        rec = flight.records()[-1]
        assert rec["cannon_mode"] == "serial", schedule  # degraded
        snap = breaker.get_board().snapshot()
        assert any(k.startswith("cannon_db|") for k in snap), schedule


def test_open_breaker_routes_serial_preemptively(mesh4):
    board = breaker.get_board()
    # a validation-class failure hard-opens the breaker immediately
    board.record_failure(ovl.DRIVER, ("mesh", "1x2x2"), kind="validation")
    assert board.state(ovl.DRIVER, ("mesh", "1x2x2")) == breaker.OPEN
    set_config(cannon_overlap="double_buffer")
    mode, why = ovl.resolve_mode("mesh", "1x2x2", 2)
    assert (mode, why) == ("serial", "breaker-open")
    a, b = _rand("A"), _rand("B", seed=4)
    clear_mesh_plans()
    out = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4)
    from dbcsr_tpu.obs import flight

    assert flight.records()[-1]["cannon_mode"] == "serial"
    ser = _mesh_ab(mesh4, "serial", a, b, alpha=1.0)
    assert (to_dense(out) == ser).all()


def test_degraded_pipeline_publishes_no_measurement(mesh4, monkeypatch):
    """A double-buffer run that degrades AFTER its tick loop ran (nan
    corruption caught by guarded's output check) must not record a
    measured overlap sample: its product came from the fused serial
    program, so banking the pipeline's timings would fabricate
    double-buffer evidence (the overlap_bench rep guard trusts this)."""
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    a, b = _rand("A"), _rand("B", seed=4)
    clean = _mesh_ab(mesh4, "double_buffer", a, b, alpha=1.0)
    metrics.reset()
    clear_mesh_plans()
    with faults.inject_faults("mesh_shift:nan,seed=97,times=1"):
        set_config(cannon_overlap="double_buffer")
        out = to_dense(sparse_multiply_distributed(1.0, a, b, 0.0, None,
                                                   mesh4))
    assert (np.asarray(out) == np.asarray(clean)).all()
    roll = stats.cannon_overlap_rollup().get("mesh", {}).get("1x2x2", {})
    assert "measured_exposed" not in roll, roll


def test_open_breaker_skips_measured_pipeline(mesh4, monkeypatch):
    """An open cannon_db breaker condemned the split per-tick programs
    themselves: even under DBCSR_TPU_SYNC_TIMING the multiply must run
    the fused serial program, not re-enter the failing pipeline
    unguarded (no measured sample may be recorded)."""
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    board = breaker.get_board()
    board.record_failure(ovl.DRIVER, ("mesh", "1x2x2"), kind="validation")
    metrics.reset()
    a, b = _rand("A"), _rand("B", seed=4)
    set_config(cannon_overlap="double_buffer")
    clear_mesh_plans()
    sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4)
    roll = stats.cannon_overlap_rollup().get("mesh", {}).get("1x2x2", {})
    assert "measured_exposed" not in roll, roll


def test_decision_on_event_bus(mesh4):
    from dbcsr_tpu.obs import events as obs_events

    obs_events.set_enabled(True)
    obs_events.clear()
    a, b = _rand("A"), _rand("B", seed=4)
    _mesh_ab(mesh4, "double_buffer", a, b)
    evs = obs_events.records(kind="cannon_overlap")
    assert evs and evs[-1]["mode"] == "double_buffer"
    assert evs[-1]["product_id"]  # correlated to the mesh multiply


def test_measured_overlap_gather_route(mesh6, monkeypatch):
    # the chunked gather publishes into the SAME measured gauge family
    # (engine="mesh", rectangular grid string) next to the ring routes
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    metrics.reset()
    a, b = _rand("A"), _rand("B", seed=4)
    db = _mesh_ab(mesh6, "double_buffer", a, b)
    ser = _mesh_ab(mesh6, "serial", a, b)
    assert (ser == db).all()
    g = metrics.gauge(ovl.MEASURED_GAUGE)
    for mode in ("double_buffer", "serial"):
        v = g.value(engine="mesh", grid="1x2x3", mode=mode)
        assert 0.0 <= v <= 1.0, (mode, v)
    roll = stats.cannon_overlap_rollup()["mesh"]["1x2x3"]
    assert 0.0 <= roll["measured_exposed"] <= 1.0
    assert roll["modeled_ratio"] > 0  # gather_chunk_model published


def test_measured_overlap_tas_route(mesh8, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    metrics.reset()
    bs_tall, bs = [4] * 12, [4] * 5
    rng = np.random.default_rng(7)
    at = make_random_matrix("AT", bs_tall, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
    set_config(cannon_overlap="double_buffer")
    clear_mesh_plans()
    tas_grouped_multiply(1.0, at, b, 0.0, None, mesh8)
    v = metrics.gauge(ovl.MEASURED_GAUGE).value(
        engine="tas", grid="2x2x2", mode="double_buffer")
    assert 0.0 <= v <= 1.0
    roll = stats.cannon_overlap_rollup()["tas"]["2x2x2"]
    assert roll["compute_s"] > 0 and roll["modeled_ratio"] > 0


def test_gather_chunk_fault_degrades_to_serial(mesh6):
    from dbcsr_tpu.obs import flight

    a, b = _rand("A"), _rand("B", seed=4)
    clean = _mesh_ab(mesh6, "double_buffer", a, b, alpha=1.0)
    for schedule in ("gather_chunk:raise,times=1",
                     "gather_chunk:nan,seed=5,times=1",
                     "gather_chunk:oom,times=1"):
        breaker.reset_board()
        clear_mesh_plans()
        with faults.inject_faults(schedule) as installed:
            set_config(cannon_overlap="double_buffer")
            out = to_dense(sparse_multiply_distributed(
                1.0, a, b, 0.0, None, mesh6))
        assert sum(s.fired for s in installed) == 1, schedule
        assert (np.asarray(out) == np.asarray(clean)).all(), schedule
        rec = flight.records()[-1]
        assert rec["cannon_mode"] == "serial", schedule  # degraded
        snap = breaker.get_board().snapshot()
        assert any(k.startswith("gather_pipe|") for k in snap), schedule


def test_tas_tick_fault_degrades_to_serial(mesh8):
    from dbcsr_tpu.obs import flight

    bs_tall, bs = [4] * 12, [4] * 5
    rng = np.random.default_rng(7)
    at = make_random_matrix("AT", bs_tall, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
    set_config(cannon_overlap="double_buffer")
    clear_mesh_plans()
    clean = to_dense(tas_grouped_multiply(1.0, at, b, 0.0, None, mesh8))
    for schedule in ("tas_tick:raise,times=1",
                     "tas_tick:nan,seed=11,times=1"):
        breaker.reset_board()
        clear_mesh_plans()
        with faults.inject_faults(schedule) as installed:
            out = to_dense(tas_grouped_multiply(1.0, at, b, 0.0, None,
                                                mesh8))
        assert sum(s.fired for s in installed) == 1, schedule
        assert (np.asarray(out) == np.asarray(clean)).all(), schedule
        rec = flight.records()[-1]
        assert rec["cannon_mode"] == "serial", schedule
        snap = breaker.get_board().snapshot()
        assert any(k.startswith("cannon_db|") and "tas" in k
                   for k in snap), schedule


def test_open_gather_breaker_routes_serial_preemptively(mesh6):
    board = breaker.get_board()
    board.record_failure(ovl.GATHER_DRIVER, ("mesh", "1x2x3"),
                         kind="validation")
    set_config(cannon_overlap="double_buffer")
    mode, why = ovl.resolve_mode("mesh", "1x2x3", 3,
                                 driver=ovl.GATHER_DRIVER)
    assert (mode, why) == ("serial", "breaker-open")
    a, b = _rand("A"), _rand("B", seed=4)
    clear_mesh_plans()
    out = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh6)
    from dbcsr_tpu.obs import flight

    assert flight.records()[-1]["cannon_mode"] == "serial"
    ser = _mesh_ab(mesh6, "serial", a, b, alpha=1.0)
    assert (to_dense(out) == ser).all()


def test_overlap_bench_smoke(tmp_path):
    """The A/B tool runs end to end on a small case: exit 0, both legs
    present, bitwise identical."""
    import json
    import subprocess

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the tool forces its own 4-device world
    env.pop("DBCSR_TPU_SYNC_TIMING", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "overlap_bench.py"),
         "--nblk", "12", "--nrep", "2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["checksum_bitwise_match"] is True
    assert set(row["ab"]) == {"serial", "double_buffer"}
    for leg in row["ab"].values():
        assert 0.0 <= leg["exposed_fraction"] <= 1.0
