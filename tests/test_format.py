"""The storage-format planner (`mm.format_planner`).

Pinned here: the planner routes each cell's product family to the
format the benchmark shows (structural gate, dense rules, stack
default, the mesh's canvas check); every format computes the
BITWISE-identical product for integer-valued operands; an injected
plan fault degrades once and is never cached; chaos block-flips under
each format are detected and healed bitwise; canvas-exceeding wide-N
products still go dense via n-chunking; and tuned kernel rows travel
the fleet tier (same device kind only).  All tier-1, CPU-only.
"""

import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu.acc import params as params_mod
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.mm import format_planner as fp
from dbcsr_tpu.mm import multiply as mm_mod
from dbcsr_tpu.obs import health, metrics
from dbcsr_tpu.ops.test_methods import to_dense
from dbcsr_tpu.resilience import breaker, faults
from dbcsr_tpu.tune import store
from dbcsr_tpu.tune import service as tune_service


@pytest.fixture(autouse=True)
def _clean_slate(tmp_path, monkeypatch):
    """Hermetic params dir + full planner/fault/metrics reset, so no
    test's promotion or chaos schedule leaks into the next."""
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod.invalidate()
    cfg0 = {f: getattr(get_config(), f)
            for f in ("abft", "mm_driver", "mm_format", "incremental",
                      "platform_override")}
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    health.reset()
    fp.reset()
    mm_mod._plan_cache.clear()
    yield tmp_path
    tune_service.stop_service()
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    health.reset()
    fp.reset()
    mm_mod._plan_cache.clear()
    set_config(**cfg0)
    params_mod.invalidate()


def _pair(nblk=8, bsize=4, fill=1.0, band=None, seed=0, dtype=np.float64):
    """A, B with integer-valued blocks: exact f64 accumulation, so C is
    bitwise-comparable across every storage format and engine."""
    rng = np.random.default_rng(seed)
    bs = [bsize] * nblk

    def _m(name, pattern):
        m = dt.create(name, bs, bs, dtype=dtype)
        rows = np.asarray([i for i, j in pattern], dtype=np.int64)
        cols = np.asarray([j for i, j in pattern], dtype=np.int64)
        blocks = rng.integers(-4, 5, size=(len(pattern), bsize, bsize)
                              ).astype(dtype)
        m.put_blocks(rows, cols, blocks)
        m.finalize()
        return m

    if band is not None:
        pattern = [(i, j) for i in range(nblk) for j in range(nblk)
                   if abs(i - j) <= band]
    else:
        pattern = [(i, j) for i in range(nblk) for j in range(nblk)
                   if rng.random() < fill]
        pattern = pattern or [(0, 0)]
    return _m("fA", pattern), _m("fB", list(pattern)), bs


def _run(fmt, a, b, bs, dtype=np.float64):
    set_config(mm_format=fmt)
    fp.reset()
    c = dt.create("fC", bs, bs, dtype=dtype)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    return c


def _dense_of(c):
    return np.asarray(to_dense(c))


def _choose(a, b, c):
    return fp.choose(a, b, c, filter_eps=None, retain_sparsity=False,
                     no_limits=True, dense=True)


def _ctr(name, **labels):
    total = 0.0
    for lb, v in metrics.counter_items(name):
        if all(lb.get(k) == val for k, val in labels.items()):
            total += v
    return total


# ------------------------------------------------- the format ladder

def test_every_format_bitwise_identical():
    """Forced stack/dense compute the same C, bit for bit, and report
    what they executed — format choice is performance only, never
    numerics."""
    a, b, bs = _pair(nblk=8, bsize=4, band=1, seed=3)
    ref = None
    executed = {}
    for fmt in ("stack", "dense"):
        c = _run(fmt, a, b, bs)
        executed[fmt] = c._mm_algorithm
        d = _dense_of(c)
        if ref is None:
            ref = d
        assert (d == ref).all(), f"{fmt} diverged bitwise"
    assert executed["stack"] == "stack"
    assert executed["dense"] == "dense"


def test_occupancy_ladder_heuristic_and_default():
    """A near-full product goes dense through the occupancy rule, a sparse one stays on the stack path,
    and both land on the decision counter."""
    set_config(mm_format="auto")
    full_a, full_b, bs = _pair(nblk=6, bsize=4, fill=1.0, seed=1)
    plan = _choose(full_a, full_b, dt.create("fC", bs, bs))
    assert (plan.fmt, plan.reason) == ("dense", "heuristic")

    sp_a, sp_b, bs = _pair(nblk=6, bsize=4, fill=0.3, seed=2)
    plan = _choose(sp_a, sp_b, dt.create("fC", bs, bs))
    assert plan.fmt == "stack"
    assert plan.reason == "default"
    assert plan.occ is not None and plan.occ < 0.5

    c = _run("auto", full_a, full_b, bs)
    assert c._mm_algorithm == "dense"
    assert _ctr("dbcsr_tpu_format_decision_total",
                format="dense", reason="heuristic") >= 1


# the north star's block grid (10 000 / 23 blocks a side): the planner
# decides from block counts and occupancy, so 4-blocks on this grid
# take the cell's own route at a CPU-sized canvas
_NS_NBLK = 10000 // 23


def _random_pair(nblk, occ, dtype, sizes=(4,), seed=0):
    rng = np.random.default_rng(seed)
    bs = [sizes[i % len(sizes)] for i in range(nblk)]
    a = dt.make_random_matrix("rA", bs, bs, dtype=dtype, occupation=occ,
                              rng=rng)
    b = dt.make_random_matrix("rB", bs, bs, dtype=dtype, occupation=occ,
                              rng=rng)
    return a, b, dt.create("rC", bs, bs, dtype=dtype)


@pytest.mark.parametrize("case,want", [
    # *.scf_f64 / *.sign_f64: the filter holds the product on the stack
    ("filtered_f64", ("stack", "structural")),
    # northstar.plain_f64: dense flops 100x the true flops
    ("northstar_f64", ("dense", "heuristic")),
    # northstar.plain_f32: a native dtype below the occupancy gate
    ("northstar_f32", ("stack", "default")),
    # mixed10k.plain_f32: blocks {5,13,23}
    ("mixed_f32", ("stack", "structural")),
    # both operands over DENSE_OCC_THRESHOLD
    ("full_f32", ("dense", "heuristic")),
    # the north star's f64 product on a 1x4 grid: no dense Cannon
    ("mesh_1x4", ("stack", "structural")),
])
def test_planner_routes_like_the_cells(case, want):
    """Each cell's product family takes the route the benchmark's
    ``dense_route_share`` shows, on a TPU as the planner sees it."""
    set_config(platform_override="tpu", mm_format="auto")
    fp.reset()
    eps = None
    dense = True
    if case == "filtered_f64":
        a, b, c = _random_pair(_NS_NBLK, 0.1, np.float64)
        eps = 1e-7
    elif case in ("northstar_f64", "mesh_1x4"):
        a, b, c = _random_pair(_NS_NBLK, 0.1, np.float64)
    elif case == "northstar_f32":
        a, b, c = _random_pair(_NS_NBLK, 0.1, np.float32)
    elif case == "mixed_f32":
        a, b, c = _random_pair(60, 0.05, np.float32, sizes=(5, 13, 23))
    else:
        a, b, c = _random_pair(8, 1.0, np.float32)
    if case == "mesh_1x4":
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 1, 4),
                    ("kl", "pr", "pc"))
        # what `sparse_multiply_distributed` can execute on this grid
        dense = mesh.shape["pr"] == mesh.shape["pc"]

    def plan():
        return fp.choose(a, b, c, filter_eps=eps, retain_sparsity=False,
                         no_limits=True, dense=dense,
                         chunked_canvas=case != "mesh_1x4")

    got = plan()
    assert (got.fmt, got.reason) == want
    if case == "northstar_f64":
        assert got.why == "cost-model:emulated-dtype"
    elif case == "full_f32":
        assert got.why == f"occupancy>={fp.DENSE_OCC_THRESHOLD}"
    elif case == "mesh_1x4":
        # a dense force the grid cannot run falls back, named as such
        set_config(mm_format="dense")
        forced = plan()
        assert (forced.fmt, forced.reason) == ("stack", "ineligible")


# --------------------------------------------------- faults and ABFT

def test_format_plan_fault_degrades_to_stack_once():
    a, b, bs = _pair(nblk=6, bsize=4, fill=1.0, seed=9)
    set_config(mm_format="auto")
    with faults.inject_faults("format_plan:raise,times=1") as sp:
        c1 = dt.create("fC1", bs, bs)
        dt.multiply("N", "N", 1.0, a, b, 0.0, c1)
        c2 = dt.create("fC2", bs, bs)
        dt.multiply("N", "N", 1.0, a, b, 0.0, c2)
    assert sp[0].fired == 1
    assert c1._mm_algorithm == "stack"   # faulted plan: degraded
    assert c2._mm_algorithm == "dense"   # transient — never cached
    assert (_dense_of(c1) == _dense_of(c2)).all()


@pytest.mark.parametrize("fmt,site", [
    ("stack", "execute_stack"),
    ("dense", "dense"),
])
def test_chaos_flip_under_each_format_heals_bitwise(fmt, site):
    """A seed-deterministic finite block-flip injected under each
    storage format is DETECTED by the ABFT layer and fully healed:
    the final C is bitwise-equal to the fault-free run (integer
    operands make even the cross-engine recompute exact)."""
    a, b, bs = _pair(nblk=8, bsize=4, band=1, seed=10)
    clean = _dense_of(_run(fmt, a, b, bs))

    set_config(abft="verify")
    set_config(mm_format=fmt)
    fp.reset()
    c = dt.create("fC", bs, bs)
    with faults.inject_faults(f"{site}:flip,seed=5,times=1") as sp:
        dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    assert sp[0].fired == 1
    assert (_dense_of(c) == clean).all()
    assert _ctr("dbcsr_tpu_abft_mismatches_total") >= 1
    assert _ctr("dbcsr_tpu_abft_recoveries_total") >= 1


# ------------------------------------------------- wide-N n-chunking

def test_wide_n_product_goes_dense_via_n_chunking(monkeypatch):
    """A C block-row wider than the canvas cap used to force the stack
    path; the n-chunked dense carve keeps it dense when profitable."""
    monkeypatch.setattr(mm_mod, "_DENSE_MAX_CANVAS", 512)
    fp.reset()
    # even ONE full-width C block-row (4*64*4 = 1024 els) overflows
    # this cap: the n axis must chunk or dense is unreachable
    chunks = mm_mod._dense_chunking(16, 64, 16, 4, 4, 4)
    assert chunks is not None
    mrb, kcb, ncb = chunks
    assert ncb < 64  # the n axis really chunks under this cap

    # a genuinely wide-N product: A 8x8 blocks, B 8x64 — one C
    # block-row is 4*256 = 1024 els, twice the cap
    rng = np.random.default_rng(12)
    rbs, cbs = [4] * 8, [4] * 64
    a = dt.create("wA", rbs, rbs)
    b = dt.create("wB", rbs, cbs)
    for m, (nr, nc) in ((a, (8, 8)), (b, (8, 64))):
        rows, cols = np.meshgrid(np.arange(nr), np.arange(nc),
                                 indexing="ij")
        m.put_blocks(rows.ravel(), cols.ravel(),
                     rng.integers(-4, 5, size=(nr * nc, 4, 4)
                                  ).astype(np.float64))
        m.finalize()
    set_config(mm_format="auto")
    fp.reset()
    c = dt.create("wC", rbs, cbs)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    assert c._mm_algorithm == "dense"

    monkeypatch.setattr(mm_mod, "_DENSE_MAX_CANVAS", 2 * 10 ** 8)
    set_config(mm_format="stack")
    fp.reset()
    ref = dt.create("wR", rbs, cbs)
    dt.multiply("N", "N", 1.0, a, b, 0.0, ref)
    assert (_dense_of(c) == _dense_of(ref)).all()


# ----------------------------------------------------- fleet sharing

class _PeerState:
    payload: dict = {}


class _PeerHandler(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
        body = json.dumps(_PeerState.payload).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # silence
        pass


@pytest.fixture
def peer_url():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _PeerHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()
    srv.server_close()


def _kernel_row(**extra):
    """A tuned kernel row: the stack engine's driver/grouping columns."""
    return {"m": 4, "n": 4, "k": 4, "dtype": "float64", "stack_size": 0,
            "driver": "xla_group", "grouping": 8, **extra}


def _peer_row():
    return {"key": [4, 4, 4, "float64", 0],
            "entry": _kernel_row(gflops=5.0, tuned_by="dbcsr_tpu.tune"),
            "generation": 3, "t_unix": 0.0}


def test_fleet_adopts_same_kind_format_promotion(peer_url):
    kind = params_mod.device_kind()
    _PeerState.payload = {"kind": kind, "rows": [_peer_row()]}
    adopted = store.peer_sync(kind=kind, peers=[peer_url])
    assert adopted == [[4, 4, 4, "float64", 0]]
    row = params_mod.lookup(4, 4, 4, "float64")
    assert (row["driver"], row["grouping"]) == ("xla_group", 8)
    assert row["adopted_from"] == peer_url
    assert _ctr("dbcsr_tpu_tune_fleet_total", event="adopted") == 1
    # adopted rows never re-export: no promotion echo around the fleet
    assert store.export_promotions(kind=kind)["rows"] == []
    # second sync: local evidence now as good — no churn
    assert store.peer_sync(kind=kind, peers=[peer_url]) == []


def test_fleet_skips_other_device_kind(peer_url):
    """Another chip's tuned row does not transfer: a kind-mismatched
    payload is counted and dropped without touching the table."""
    _PeerState.payload = {"kind": "definitely_not_this_kind",
                          "rows": [_peer_row()]}
    assert store.peer_sync(peers=[peer_url]) == []
    assert params_mod.lookup(4, 4, 4, "float64") is None
    assert _ctr("dbcsr_tpu_tune_fleet_total", event="kind_mismatch") == 1


def test_promotions_route_serves_origin_rows():
    from dbcsr_tpu.obs import server

    store.promote(_kernel_row(gflops=9.9))
    kind = params_mod.device_kind()
    server.start(port=0)
    try:
        with urllib.request.urlopen(
                f"{server.url()}/tune/promotions?kind={kind}",
                timeout=30) as r:
            payload = json.loads(r.read().decode())
    finally:
        server.stop()
    assert payload["kind"] == kind
    assert len(payload["rows"]) == 1
    entry = payload["rows"][0]["entry"]
    assert (entry["driver"], entry["grouping"]) == ("xla_group", 8)


# ------------------------------------------------------------- knobs

def test_format_knob_validation():
    with pytest.raises(ValueError):
        set_config(mm_format="bogus")
    with pytest.raises(ValueError):
        set_config(mm_format="composite")
    set_config(mm_format="dense")
    assert get_config().mm_format == "dense"
