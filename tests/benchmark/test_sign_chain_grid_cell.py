"""The cell `h2o_ls_chain_2x2.sign_f64` on the CPU (four of conftest's
virtual devices), beyond the tiny run every cell gets in
`test_benchmark_harness.py`: the configuration is `h2o_ls_chain`'s but
for the grid, the generator is `sign_chain`'s but for the mesh, a traced
rehearsal gives every listed per-layer metric a value (over a
hand-written trace of two chains on four devices, worked out by hand
below), and an X that is wrong, by a part in 1e9 or by a float32 chain,
is not `correct`.  Counts and results only; no number here is a rate."""

import json
import os
import types

import numpy as np
import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, opmeta, reference, xplane
from benchmark.fixtures.tiny import REPO, tiny_checkout

CELL = "h2o_ls_chain_2x2.sign_f64"
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
BENCH = types.SimpleNamespace(arithmetic=arithmetic, reference=reference)
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
LISTED = {m["name"] for m in SPEC["per_layer"]
          if "workloads" not in m or CELL in m["workloads"]}


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("tiny_grid_chain")))


@pytest.fixture()
def restore_config():
    prev = dt.get_config().incremental
    yield
    dt.set_config(incremental=prev)


def _hand_written(monkeypatch, stem: str) -> dict:
    """Put a hand-written trace of `fixtures/` (`<stem>_trace.json`,
    `<stem>_opmeta.json`) in place of what a CPU run cannot record."""
    with open(os.path.join(FIXTURES, stem + "_trace.json")) as fh:
        trace = json.load(fh)
    with open(os.path.join(FIXTURES, stem + "_opmeta.json")) as fh:
        meta = {k: v for k, v in json.load(fh).items()
                if not k.startswith("_")}
    monkeypatch.setattr(xplane, "load", lambda path, keep=None: trace)
    monkeypatch.setattr(opmeta, "device_ops", lambda path: meta)
    info = harness._device_info
    monkeypatch.setattr(harness, "_device_info",
                        lambda devs: dict(info(devs), kind="TPU v5 lite"))
    return trace


@pytest.fixture()
def grid_chain_trace(monkeypatch):
    """Two chains on four devices, worked out by hand below."""
    return _hand_written(monkeypatch, "synthetic_grid_chain")


# ------------------------------------------------------ the files
def test_configuration_is_h2o_ls_chain_on_the_grid():
    one, grid = _config("h2o_ls_chain.json"), _config("h2o_ls_chain_2x2.json")
    moved = {k for k in set(one) | set(grid) if one.get(k) != grid.get(k)}
    assert moved == {"name", "source", "grid", "chips", "operation",
                     "guarantees", "reduced_why", "assumed"}
    assert grid["grid"] == [2, 2] and grid["chips"] == 4
    assert grid["architecture"] is None and grid["reduced"] == ["m", "n", "k"]
    # the recipe of H and the chain's limits, key for key
    assumed = dict(grid["assumed"])
    assert assumed.pop("grid")["value"] == [2, 2]
    assert assumed == one["assumed"]
    # no tolerance loosened for the layout: three guarantees word for
    # word, the fourth with the same limits
    for key in ("determinism", "no_failover", "flops"):
        assert grid["guarantees"][key] == one["guarantees"][key]
    for limit in ("arithmetic.reference_tolerance(float64, 23, 435) x the "
                  "number of products", "6.4e-13 for 14 products",
                  "within 1e-6 of n - 2 x occupied (6522)"):
        assert limit in grid["guarantees"]["reference"]
        assert limit in one["guarantees"]["reference"]


def test_generator_is_sign_chains_but_for_the_mesh(tiny):
    """Nothing of the yardstick is copied: the reference chain, the
    stacks, the flops, the tolerance and the check are the functions of
    `sign_chain.py`, loaded from the file beside the generator."""
    cell = harness.Cell(tiny, CELL)
    grid_gen = cell.generator
    assert cell.traffic["generator"] == "sign_chain_grid" and cell.chips == 4
    assert grid_gen.sign_chain.__file__ == os.path.join(
        tiny, "benchmark", "generators", "sign_chain.py")
    base = grid_gen.sign_chain.Generator
    assert issubclass(grid_gen.Generator, base)
    own = {k for k, v in vars(grid_gen.Generator).items() if callable(v)}
    assert own == {"__init__", "make_operands", "start"}
    with pytest.raises(ValueError, match="grid 1,1"):
        base(BENCH, cell.config, cell.traffic, 1, [])
    import jax

    gen = grid_gen.Generator(BENCH, cell.config, cell.traffic, 5,
                             jax.devices()[:4])
    assert gen.make_operands()["grid"] == [2, 2]
    assert dict(gen.mesh.shape) == {"kl": 1, "pr": 2, "pc": 2}


# ------------------------------------------------- the traced rehearsal
def test_traced_rehearsal_gives_every_listed_metric_a_value(
        tiny, grid_chain_trace, capsys, restore_config):
    assert harness.run_cell(tiny, CELL, 3, 0.3, True, platform="cpu") == 0
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == LISTED
    n = line["attempted"]
    chain = [json.loads(ln.split(" ", 2)[2]) for ln in out
             if ln.startswith("BENCH chain ")]

    # counters: what the chains of the window did, per chain
    assert got["candidates_pruned"] == sum(p["pruned"] for p in chain) > 0
    assert got["filter_dropped_blocks"] == \
        sum(p["c_dropped"] for p in chain) > 0
    assert got["compiles_in_window"] == 0 and got["dense_route_share"] == 0
    # every kind of mesh program ran at some shape in set-up, and the
    # earlier line says which
    shapes = json.loads(next(ln for ln in out if ln.startswith(
        "BENCH mesh_program_shapes ")).split(" ", 2)[2])["series"]
    assert {k.split("=")[1] for k in shapes} >= {
        "assembly", "tick", "shift", "finish", "collect"}
    assert got["mesh_program_shapes"] == sum(shapes.values()) >= 5

    # the hand-written trace, by hand (ns over the window / chains):
    # collect: device 0 fusion 300 + all-reduce 100 + 200, device 1 700
    assert got["mesh_collect_s"] == pytest.approx(700e-9 / n)
    # norms 100 + compress 200 on device 0; compress 100 on device 1
    assert got["mesh_compress_s"] == pytest.approx(300e-9 / n)
    # the ticks: device 0 600 + 400 (gather 100 + 100, dot 200 + 300,
    # scatter 50, the while itself 150, a copy 100), device 1 one dot 800
    assert got["stack_launch_s"] == pytest.approx(1000e-9 / n)
    assert got["stack_dot_s"] == pytest.approx(800e-9 / n)
    assert got["stack_gather_s"] == pytest.approx(200e-9 / n)
    assert got["stack_accum_s"] == pytest.approx(50e-9 / n)
    # the ring shift's permute 100 and the collect's all-reduce 100
    assert got["collective_s"] == pytest.approx(200e-9 / n)
    # union add 250 + scale 50
    assert got["chain_add_s"] == pytest.approx(300e-9 / n)
    # sign_step 3800 - (1500 + 1000 + 300 + 400), and 2800 - 2000
    assert got["chain_host_s"] == pytest.approx(1400e-9 / n)
    # mesh_plan_build 500 - (100 + 200), and 300
    assert got["mesh_plan_host_s"] == pytest.approx(500e-9 / n)
    # device 2 is busy 300 of the window's 10 000
    assert got["least_busy_device_share"] == pytest.approx(3.0)
    assert got["mesh_stack_hbm_share"] > 0


def test_a_metric_of_the_grid_finds_nothing_in_another_cells_trace(
        tiny, monkeypatch, capsys, restore_config):
    """Over the benchmark's first hand-written trace (one chip's
    programs) the module and span readers of the grid find nothing and
    say nothing; the counters still read."""
    _hand_written(monkeypatch, "synthetic")
    assert harness.run_cell(tiny, CELL, 4, 0.3, True, platform="cpu") == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    silent = {"mesh_collect_s", "mesh_compress_s", "mesh_plan_host_s",
              "chain_add_s", "chain_host_s"}
    assert set(line["metrics"]) == LISTED - silent
    assert line["metrics"]["mesh_program_shapes"]["value"] >= 5


# ------------------------------------------------------- a wrong X
def test_an_x_off_by_a_part_in_1e9_is_not_correct(
        tiny, capsys, monkeypatch, restore_config):
    from dbcsr_tpu.ops.operations import scale

    generator = harness.Cell(tiny, CELL).generator.Generator
    start = generator.start

    def off(self, product):
        x, flops = start(self, product)
        return scale(x, 1.0 + 1e-9), flops

    monkeypatch.setattr(generator, "start", off)
    assert harness.run_cell(tiny, CELL, 3, 0.1, False, platform="cpu") == 0
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1])["correct"] is False
    check = json.loads(next(ln for ln in out if ln.startswith(
        "BENCH check ")).split(" ", 2)[2])["0"]
    assert check["same_pattern"] and check["steps"] == check["steps_reference"]
    assert check["tol"] < check["rel_err"] < 1e-8


@pytest.mark.parametrize("variant", ["float32", "no_filter"])
def test_a_chain_computed_otherwise_is_not_correct_on_the_grid(
        tiny, variant):
    """The grid cell's check is `sign_chain`'s: a chain in float32 or
    without the filter fails it by orders, whatever the layout."""
    import jax

    cell = harness.Cell(tiny, CELL)
    gen = cell.generator.Generator(BENCH, cell.config, cell.traffic, 6,
                                   jax.devices()[:4])
    gen.make_operands()
    sc = cell.generator.sign_chain
    kw = {"float32": {"compute": np.float32},
          "no_filter": {"drop": False}}[variant]
    other = sc.reference_chain(gen.h, filter_eps=gen.filter_eps, tol=gen.tol,
                               max_steps=gen.max_steps, **kw)
    sizes = other.x.sizes.astype(np.int32)
    staged = dt.create("X", sizes, sizes, "float64")
    for rows, cols, data in other.x.by_shape():
        staged.put_blocks(rows, cols, data)
    gen._history = other.history
    check = gen.check(0, staged.finalize())
    flops = arithmetic.true_flops(sc.chain_stacks(other.products))
    assert not (check["ok"] and flops == gen.flops(0)), check
    if variant == "float32":
        assert check["rel_err"] > 1e4 * check["tol"]
    else:
        assert not check["same_pattern"]
