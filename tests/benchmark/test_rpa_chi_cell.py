"""The cell `rpa_h2o32.chi_f64` on the CPU: the configuration states its
source, its cuts, its assumed sizes and the four guarantees; the
traffic takes the configuration's batches in turn; a rehearsal at two
molecules is `correct` with the program's flops equal to the
reference's; the four metrics the cell brings read what a traced
window holds and say nothing where nothing is there; and the cell stays
off the lists that other tests pin.  Counts and results only; no number
here is a rate."""

import itertools
import json
import os
import types

import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, reference, xplane
from benchmark.fixtures.tiny import REPO, tiny_checkout

CELL = "rpa_h2o32.chi_f64"
NEW = ("tensor_remap_s", "tensor_remap_hbm_share", "contract_host_s",
       "tas_groups_per_product")
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
with open(os.path.join(REPO, "benchmark", "configs", "rpa_h2o32.json")) as _fh:
    CONFIG = json.load(_fh)
with open(os.path.join(REPO, "benchmark", "traffic", "chi_f64.json")) as _fh:
    TRAFFIC = json.load(_fh)
BENCH = types.SimpleNamespace(arithmetic=arithmetic, reference=reference)


@pytest.fixture(scope="module")
def two_waters(tmp_path_factory):
    """A tiny checkout whose `rpa_h2o32` box holds two waters in two
    batches (m = 2 x 84 RI functions)."""
    root = tiny_checkout(str(tmp_path_factory.mktemp("tiny_rpa")))
    path = os.path.join(root, "benchmark", "configs", "rpa_h2o32.json")
    cut = dict(CONFIG, m=168, n=168, k=46,
               recipe=dict(CONFIG["recipe"], batches=2))
    with open(path, "w") as fh:
        json.dump(cut, fh)
    return root


@pytest.fixture()
def restore_config():
    prev = dt.get_config().incremental
    yield
    dt.set_config(incremental=prev)


def _lines(capsys):
    out = capsys.readouterr().out.splitlines()
    bench = {}
    for ln in out[:-1]:
        if ln.startswith("BENCH "):
            tag, body = ln.split(" ", 2)[1:]
            bench.setdefault(tag, []).append(json.loads(body))
    return json.loads(out[-1]), bench


# ------------------------------------------------------ the configuration
def test_the_configuration_states_its_source_cuts_and_guarantees():
    entry = {c["name"]: c for c in SPEC["configs"]}["rpa_h2o32"]
    assert CONFIG["source"] == entry["source"]
    assert "32-H2O" in CONFIG["source"] and "LOW_SCALING" in CONFIG["source"]
    assert set(CONFIG["guarantees"]) == {"reference", "determinism",
                                         "no_failover", "flops"}
    assert CONFIG["reduced"] == entry["reduced"] == ["m", "n", "k"]
    assert "32" in CONFIG["reduced_why"]
    for key in ("ao_per_atom", "ri_per_atom", "cutoff", "decay", "tau",
                "filter_eps", "batches"):
        assert CONFIG["assumed"][key]["why"]
    for key in ("ao_per_atom", "ri_per_atom", "cutoff", "decay", "tau",
                "filter_eps", "batches"):
        assert CONFIG["recipe"][key] == CONFIG["assumed"][key]["value"]
    # the box is m's: 32 waters of 84 RI and 23 AO functions
    ri, ao = sum(CONFIG["recipe"]["ri_per_atom"]), sum(
        CONFIG["recipe"]["ao_per_atom"])
    assert (CONFIG["m"], CONFIG["n"], CONFIG["k"]) == (32 * ri, 32 * ri,
                                                       32 * ao)
    assert (CONFIG["grid"], CONFIG["chips"]) == ([1, 1], 1)
    assert CONFIG["filter_eps"] == CONFIG["recipe"]["filter_eps"] == 1e-9


def test_the_cell_and_its_metrics_are_appended_where_they_belong():
    cell = {w["name"]: w for w in SPEC["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "rpa_h2o32", "chi_f64", 1)
    assert SPEC["workloads"][-1]["name"] == CELL
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    assert [m["name"] for m in SPEC["per_layer"][-len(NEW):]] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "tensor layer"
    for name in ("stack_launch_s", "stack_gather_s", "stack_dot_s",
                 "stack_accum_s", "stack_hbm_share", "index_host_s",
                 "launches_per_multiply", "plan_cache_misses"):
        assert by_name[name]["workloads"][-1] == CELL
    # off the pinned lists, off filter_host_s (a batch's multiplies run
    # unfiltered: its filter is contract_host_s's) and off peak_hbm_gib
    for name in ("stack_slot_fill", "stack_grouped_share",
                 "setup_validate_s", "filter_host_s"):
        assert CELL not in by_name[name]["workloads"]
    peak = {m["name"]: m for m in SPEC["end_to_end"]}["peak_hbm_gib"]
    assert CELL not in peak["workloads"]


def test_the_traffic_takes_every_batch_in_turn():
    assert TRAFFIC["generator"] == "rpa_chi"
    assert TRAFFIC["dtype"] == "float64"
    assert TRAFFIC["program_config"] == {"incremental": "off"}
    gen = harness._load_code(os.path.join(
        REPO, "benchmark", "generators", "rpa_chi.py")).Generator(
            BENCH, CONFIG, TRAFFIC, 7, [])
    gen.dep = types.SimpleNamespace(batches=8)
    assert gen.molecules() == 32
    assert gen.distinct_products() == list(range(8))
    assert list(itertools.islice(gen.schedule(), 17)) == \
        list(range(8)) * 2 + [0]


# ---------------------------------------------------------- rehearsals
def test_a_rehearsal_at_two_waters_is_correct(two_waters, capsys,
                                              restore_config):
    rc = harness.run_cell(two_waters, CELL, 2147483659, 0.3, False,
                          platform="cpu")
    line, bench = _lines(capsys)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    checks = bench["check"][0]
    assert sorted(checks) == ["0", "1"]  # both batches checked
    for check in checks.values():
        assert check["ok"] and check["flops_program"] == \
            check["flops_true"] > 0
        assert check["rel_err"] <= check["tol"]
        # one P row of each RI block size at least: every bin of chi
        assert {(14, 14), (14, 56), (56, 14), (56, 56)} <= {
            tuple(b) for b in check["bins"]}
    warm = bench["warmup"][0]
    assert {w["product"] for w in warm} == {0, 1}


def _trace(products):
    """A hand-written trace of ``products`` batches: per batch, the
    tensor layer's spans with a multiply nested in each contraction,
    the deferred filter, and on the device a remap and a staging
    scatter module."""
    host, modules, ops = [], [], []
    for i in range(products):
        t = 1_000_000 * i
        host += [["bench:product", t + 1000, 900_000],
                 ["dbcsr_tpu:tensor_contract", t + 2000, 300_000],
                 ["dbcsr_tpu:tensor_remap", t + 3000, 20_000],
                 ["dbcsr_tpu:tas_multiply", t + 30_000, 200_000],
                 ["dbcsr_tpu:tensor_batch_filter", t + 400_000, 10_000]]
        modules += [[f"jit__remap_rows({i})", t + 5000, 4000],
                    [f"jit__scatter_staged({i})", t + 10_000, 1000]]
        ops += [["fusion.1", t + 5000, 4000], ["scatter.2", t + 10_000, 1000]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]}


def test_a_traced_rehearsal_reads_the_tensor_metrics(
        two_waters, capsys, monkeypatch, restore_config):
    monkeypatch.setattr(xplane, "load",
                        lambda path, keep=None: _trace(products=3))
    info = harness._device_info
    monkeypatch.setattr(harness, "_device_info",
                        lambda devs: dict(info(devs), kind="TPU v5 lite"))
    rc = harness.run_cell(two_waters, CELL, 3, 0.3, True, platform="cpu")
    line, bench = _lines(capsys)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    n = line["attempted"]
    # three contractions a batch, one TAS group each
    assert got["tas_groups_per_product"]["value"] == 3
    assert bench["dbcsr_tpu_tas_groups_total"][0] == {"m": 2 * n, "k": n}
    # self times: contract less its nested remap and multiply, the
    # remap, the filter; per product of the window
    want_host = (300_000 - 20_000 - 200_000 + 20_000 + 10_000) * 3 / n
    assert got["contract_host_s"]["value"] == pytest.approx(want_host * 1e-9)
    assert got["tensor_remap_s"]["value"] == pytest.approx(5000 * 3 / n
                                                           * 1e-9)
    share = got["tensor_remap_hbm_share"]["value"]
    roof = bench["tensor_remap_s_roofline"][0]
    assert share == pytest.approx(
        100 * roof["bytes"] / (roof["seconds"] * 819e9))
    assert share > 0


def test_the_new_readers_say_nothing_where_nothing_is_there():
    """On a trace without the tensor layer's spans and modules (the
    parent's program, or another cell) and with a generator that
    counts no remap bytes, each new metric is left out, not read as
    0."""
    ctx = types.SimpleNamespace(
        run=types.SimpleNamespace(
            trace={"planes": [{"name": "/host:CPU", "lines": []}]},
            trace_window=(0, 1), records=[{}], product_ids=[0],
            metrics={}, counters_before={}, counters_after={},
            algorithms=[None]),
        xplane=xplane, family=harness.HOST_SPAN_FAMILY, devices=[None],
        gen=object(), peaks={"hbm_gbytes_per_s": 819.0}, log=print,
        cell=harness.Cell(REPO, CELL))
    for name in ("contract_host_s", "tensor_remap_s",
                 "tensor_remap_hbm_share"):
        spec, reducer = ctx.cell.layer(name)
        assert reducer.reduce(spec, ctx) is None, name
