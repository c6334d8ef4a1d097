"""The benchmark's harness on the CPU: every cell at a few blocks, the
no-chip exit, the trace reductions on traces kept with the benchmark,
the contract's limits on `BENCHMARK.json`, and that a new cell, traffic
mix and per-layer metric need no edit to a file that is there.

A CPU run says whether the harness is right and what it counts; no
number here is a rate.
"""

import json
import os
import re
import shutil

import numpy as np
import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, reference, xplane
from benchmark.fixtures.tiny import REPO, tiny_checkout

FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
FAMILY = harness.HOST_SPAN_FAMILY
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
CELLS = [w["name"] for w in SPEC["workloads"]]
E2E = [m["name"] for m in SPEC["end_to_end"]]

LAYER_METRICS = [m["name"] for m in SPEC["per_layer"]]


def _e2e_of(cell: str) -> set:
    """The end-to-end metrics `BENCHMARK.json` gives this cell."""
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", CELLS)}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture()
def synthetic():
    with open(os.path.join(FIXTURES, "synthetic_trace.json")) as fh:
        return json.load(fh)


@pytest.fixture()
def restore_config():
    prev = dt.get_config().incremental
    yield
    dt.set_config(incremental=prev)


def _last_line(capsys):
    out = capsys.readouterr().out.splitlines()
    return json.loads(out[-1]), out[:-1]


# ------------------------------------------------------- rehearsal, CPU
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_tiny_and_prints_the_contract_line(
        cell, tiny, capsys, restore_config):
    # the 2x2 cell takes four of conftest's virtual CPU devices
    rc = harness.run_cell(tiny, cell, 3, 0.3, False, platform="cpu")
    line, earlier = _last_line(capsys)
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == _e2e_of(cell)
    units = {m["name"]: m["unit"] for m in SPEC["end_to_end"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and m["value"] >= 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    tags = {ln.split()[1] for ln in earlier if ln.startswith("BENCH ")}
    assert {"cell", "operands", "warmup", "check", "compile_cache",
            "memory_at_window_start", "multiply_s", "window"} <= tags


def test_main_without_a_chip_exits_nonzero_naming_the_platform(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc == 2 and cap.out == "" and "cpu" in cap.err


def test_cell_asking_for_more_chips_than_there_are_exits_nonzero(
        tiny, capsys, monkeypatch):
    import jax

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    rc = harness.run_cell(tiny, "northstar_2x2.plain_f64", 1, 0.1, False,
                          platform="cpu")
    cap = capsys.readouterr()
    assert rc == 2 and cap.out == "" and "needs 4" in cap.err


def test_wrong_answer_is_not_correct(tiny, capsys, monkeypatch,
                                     restore_config):
    # a reference that disagrees: the line still prints, correct is false
    monkeypatch.setattr(arithmetic, "reference_tolerance",
                        lambda *a: -1.0)
    rc = harness.run_cell(tiny, "northstar.plain_f64", 3, 0.1, False,
                          platform="cpu")
    line, _ = _last_line(capsys)
    assert rc == 0 and line["correct"] is False


def test_unknown_device_kind_is_an_error(tiny):
    cell = harness.Cell(tiny, CELLS[0])
    assert cell.peaks("TPU v5 lite")["hbm_gbytes_per_s"] == 819.0
    with pytest.raises(harness.BenchError, match="peaks.json"):
        cell.peaks("TPU v9 imaginary")


def _traced(tiny, cell, synthetic, monkeypatch, capsys):
    """A traced run on the CPU: the profiler really runs, but a CPU
    trace has no device plane, so the reductions are given the
    hand-written one."""
    monkeypatch.setattr(xplane, "load", lambda path, keep=None: synthetic)
    info = harness._device_info
    monkeypatch.setattr(harness, "_device_info",
                        lambda devs: dict(info(devs), kind="TPU v5 lite"))
    rc = harness.run_cell(tiny, cell, 3, 0.3, True, platform="cpu")
    assert rc == 0
    return _last_line(capsys)


def test_traced_run_reports_layers_device_times_and_breakdown(
        tiny, synthetic, monkeypatch, capsys, restore_config):
    line, earlier = _traced(tiny, "northstar.scf_f64", synthetic,
                            monkeypatch, capsys)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    want = {m["name"] for m in SPEC["per_layer"]
            if "workloads" not in m
            or "northstar.scf_f64" in m["workloads"]}
    # no dbcsr_tpu:multiply_filter span in the hand-written trace: that
    # reader finds nothing and the metric is left out, not reported as 0
    assert set(line["metrics"]) == want - {"filter_host_s"}
    assert not set(line["metrics"]) & set(E2E)
    assert line["device"]["window_s"] == pytest.approx(8e-6)
    assert line["device"]["busy_s"] == pytest.approx(5e-6)
    assert line["metrics"]["device_idle_share"]["value"] == pytest.approx(37.5)
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["dense_route_share"]["value"] == 0  # CPU: stacks
    assert line["metrics"]["launches_per_multiply"]["value"] > 0
    # the fused module's 2500 ns over the run's products, against the
    # bytes of this run's stacks at 819 GB/s
    launch_s = line["metrics"]["stack_launch_s"]["value"]
    assert launch_s == pytest.approx(2500e-9 / line["attempted"])
    stacks = json.loads(next(ln for ln in earlier if ln.startswith(
        "BENCH stacks ")).split(" ", 2)[2])["0"]
    assert line["metrics"]["stack_hbm_share"]["value"] == pytest.approx(
        100 * arithmetic.fused_stack_bytes(stacks, 8) / launch_s / 819e9)
    for key in ("device_ops", "idle_gaps"):
        rows = line["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
    assert line["breakdown"]["idle_gaps"][0] == ["bench:fence",
                                                 pytest.approx(1e-6)]


def test_traced_mesh_cell_reads_the_algorithm_where_nothing_is_counted(
        tiny, synthetic, monkeypatch, capsys, restore_config):
    line, earlier = _traced(tiny, "northstar_2x2.plain_f64", synthetic,
                            monkeypatch, capsys)
    # the mesh path counts no format decision; on the CPU its result
    # says "stack", so the share is 0 and says so on an earlier line
    assert line["metrics"]["dense_route_share"]["value"] == 0
    assert any('"uncounted": true' in ln for ln in earlier)
    # four devices asked, two in the hand-written trace
    assert line["metrics"]["least_busy_device_share"]["value"] == \
        pytest.approx(12.5)
    assert line["metrics"]["device_idle_share"]["value"] == \
        pytest.approx(62.5)
    assert line["metrics"]["collective_s"]["value"] > 0


# ------------------------------------- adding cells is adding files only
MIXED = {"m": 120, "n": 120, "k": 120,
         "blocks": {d: [[1, 5], [1, 13], [1, 23]] for d in "mnk"},
         "occupancy": {"a": 0.4, "b": 0.4}, "pattern_seed": 11,
         "trans": ["N", "N"], "alpha": 1.0, "beta": 0.0, "filter_eps": 1e-7,
         "grid": [1, 1], "chips": 1}


def _snapshot(root):
    held = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                held[p] = fh.read()
    return held


def _add_to_lists(root, **lists):
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    for key, entries in lists.items():
        spec[key].extend(entries)
    # an end-to-end metric that names its cells gets the new one-chip
    # cells too: one more name in a list of BENCHMARK.json
    for cell in lists.get("workloads", []):
        for m in spec["end_to_end"]:
            if "workloads" in m and cell["chips"] == 1:
                m["workloads"].append(cell["name"])
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)


def test_new_config_cell_traffic_and_layer_metric_need_no_edit(
        tmp_path, synthetic, monkeypatch, capsys, restore_config):
    # a later PR's mixed-blocks deployment: three block sizes on every
    # dimension, so 27+ (m,n,k) triples and 9+ C bins
    root = tiny_checkout(str(tmp_path))
    before = _snapshot(root)
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", "mixed_later.json"), "w") as fh:
        json.dump(MIXED, fh)
    with open(os.path.join(bdir, "traffic", "filtered_f32.json"), "w") as fh:
        json.dump({"generator": "repeat_product", "dtype": "float32",
                   "filter": True, "program_config": {"incremental": "off"},
                   "why": "a later PR's mix"}, fh)
    with open(os.path.join(bdir, "layers", "c_assemble_host_s.json"),
              "w") as fh:
        json.dump({"layer": "index", "unit": "s", "moves": "multiply_s",
                   "source": "program_span", "reducer": "host_span_self",
                   "span": "dbcsr_tpu:multiply_stacks"}, fh)
    cell = "mixed_later.filtered_f32"
    _add_to_lists(
        root,
        configs=[{"name": "mixed_later", "source": "a later PR's source",
                  "file": "benchmark/configs/mixed_later.json",
                  "reduced": [], "why": "a later PR's deployment"}],
        workloads=[{"name": cell, "config": "mixed_later",
                    "traffic": "filtered_f32", "chips": 1,
                    "why": "a later PR's cell"}],
        per_layer=[{"name": "c_assemble_host_s", "unit": "s",
                    "better": "lower", "source": "program_span",
                    "layer": "index", "moves": "multiply_s",
                    "workloads": [cell]}])

    rc = harness.run_cell(root, cell, 5, 0.2, False, platform="cpu")
    line, earlier = _last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == set(E2E)
    # the reference was held to a row of every block size, so to every
    # (m, n) bin of C
    check = json.loads(next(ln for ln in earlier if ln.startswith(
        "BENCH check ")).split(" ", 2)[2])["0"]
    sizes = arithmetic.expand_block_sizes(120, MIXED["blocks"]["m"])
    assert check["row_block_sizes"] == sorted(set(sizes.tolist()))
    line, _ = _traced(root, cell, synthetic, monkeypatch, capsys)
    # self time of the hand-written trace's one multiply_stacks span,
    # over the products of this run
    assert line["metrics"]["c_assemble_host_s"]["value"] == pytest.approx(
        500e-9 / line["attempted"])
    after = _snapshot(root)
    assert {p: after[p] for p in before} == before


def test_a_cell_over_files_that_are_there_needs_only_list_entries(
        tmp_path, capsys, restore_config):
    # `traffic/plain_f32.json` waits for its first cell (PERF.md, Open
    # questions): the north star in float32 is two list entries away
    root = tiny_checkout(str(tmp_path))
    before = _snapshot(root)
    _add_to_lists(root, workloads=[{
        "name": "northstar.plain_f32", "config": "northstar",
        "traffic": "plain_f32", "chips": 1, "why": "a later PR's cell"}])
    rc = harness.run_cell(root, "northstar.plain_f32", 2, 0.2, False,
                          platform="cpu")
    line, earlier = _last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert any('"dtype": "float32"' in ln for ln in earlier)
    assert _snapshot(root) == before


# ------------------------------------------------- the trace reductions
W = (1000.0, 9000.0)  # the two bench:product spans of the synthetic trace


def test_busy_union_and_idle_share_by_hand(synthetic):
    (_, dev0), (_, dev1) = xplane.device_planes(synthetic)
    ops = xplane.clip(xplane.line_events(dev0, xplane.OPS_LINE), W)
    # fusion.0 lies before the window; fusion.4 is nested in while.3
    assert xplane.union_ns(ops) == 5000.0
    assert xplane.gaps(ops, W) == [(1000, 2000), (4500, 6000), (8500, 9000)]
    ops1 = xplane.clip(xplane.line_events(dev1, xplane.OPS_LINE), W)
    assert xplane.union_ns(ops1) == 1000.0


def test_time_by_module_and_op_by_hand(synthetic):
    dev0 = xplane.device_planes(synthetic)[0][1]
    sec = xplane.device_seconds
    assert sec(dev0, W, ["jit_fused*"]) == pytest.approx(2500e-9)
    assert sec(dev0, W, ["jit__dense*"], ["*dot*"]) == pytest.approx(1500e-9)
    assert sec(dev0, W, ["jit__dense*"], ["copy*"]) == pytest.approx(1000e-9)
    assert sec(dev0, W, ["jit_warm*"]) == 0.0  # outside the window
    by = {(m, n): s for m, n, s in xplane.ops_by_module(dev0, W)}
    assert by[("jit_fused", "while.3")] == 600  # less its body's 400
    assert sum(by.values()) == 5000  # self times add up to the union
    assert xplane.module_name("jit_fused(11)") == "jit_fused"
    assert xplane.op_kind("%fusion.123 = f32[8]") == "fusion"
    assert xplane.op_kind("all-reduce.7") == "all-reduce"


def test_host_span_self_time_by_hand(synthetic):
    self_ns = xplane.span_self_ns
    # 600 (PjitFunction is not of the family) and 300 - 100
    assert self_ns(synthetic, "dbcsr_tpu:multiply_index", FAMILY, W) == \
        [600, 200]
    assert self_ns(synthetic, "dbcsr_tpu:multiply", FAMILY, W) == [200, 500]
    assert self_ns(synthetic, "dbcsr_tpu:absent", FAMILY, W) == []


def test_gap_attribution_by_hand(synthetic):
    dev0 = xplane.device_planes(synthetic)[0][1]
    ops = xplane.clip(xplane.line_events(dev0, xplane.OPS_LINE), W)
    got = xplane.attribute_gaps(xplane.gaps(ops, W), synthetic, FAMILY)
    assert got == {"bench:fence": 1000, "dbcsr_tpu:multiply_index": 800,
                   "dbcsr_tpu:multiply": 500,
                   "dbcsr_tpu:multiply_stacks": 200, "bench:dispatch": 200,
                   "host:none": 200, "dbcsr_tpu:native_candidates": 100}
    assert sum(got.values()) == 8000 - 5000
    assert xplane.top(got, 2) == [["bench:fence", pytest.approx(1e-6)],
                                  ["dbcsr_tpu:multiply_index",
                                   pytest.approx(8e-7)]]


# ------------------------------------------------------- the arithmetic
def test_north_star_blocks_and_tolerances():
    sizes = arithmetic.expand_block_sizes(10000, [[1, 23]])
    assert len(sizes) == 435 and sizes[-1] == 18 and set(sizes[:-1]) == {23}
    mixed = arithmetic.expand_block_sizes(20000, [[1, 5], [1, 13], [1, 23]])
    assert mixed.sum() == 20000 and set(mixed[:-1]) == {5, 13, 23}
    f64 = arithmetic.reference_tolerance("float64", 23, 435)
    f32 = arithmetic.reference_tolerance("float32", 23, 435)
    assert 4e-14 < f64 < 5e-14 and 2e-5 < f32 < 3e-5
    with pytest.raises(KeyError):
        arithmetic.reference_tolerance("bfloat16", 23, 435)


def test_stack_arithmetic_against_brute_force():
    rng = np.random.default_rng(0)
    ms = arithmetic.expand_block_sizes(40, [[1, 3], [1, 5]])
    a = reference.draw_blocks(rng, rng, ms, ms, 0.5, "float64")
    b = reference.draw_blocks(rng, rng, ms, ms, 0.5, "float64")
    stacks = arithmetic.product_stacks(a.rows, a.cols, b.rows, b.cols,
                                       ms, ms, ms)
    flops, entries, c_blocks = 0, 0, set()
    b_at = {}
    for r, c in zip(b.rows, b.cols):
        b_at.setdefault(int(r), []).append(int(c))
    for i, k in zip(a.rows, a.cols):
        for j in b_at.get(int(k), []):
            flops += 2 * int(ms[i]) * int(ms[j]) * int(ms[k])
            entries += 1
            c_blocks.add((int(i), int(j)))
    assert arithmetic.true_flops(stacks) == flops
    assert sum(s[3] for s in stacks) == entries
    assert sum({(m, n): cb for m, n, _, _, cb in stacks}.values()) == \
        len(c_blocks)
    one = [(23, 23, 23, 1000, 400)]
    assert arithmetic.fused_stack_bytes(one, 8) == \
        arithmetic.stack_bytes(23, 23, 23, 1000, nseg=400)
    assert arithmetic.stack_flops(23, 23, 23, 1000) == 2 * 23 ** 3 * 1000
    assert arithmetic.dense_cost(2, 3, 4)["flops"] == 48
    q = arithmetic.quartiles([4, 1, 3, 2])
    assert (q["n"], q["median"], q["q1"], q["q3"]) == (4, 2.5, 1.75, 3.25)


def test_operand_draw_is_the_programs_and_the_reference_is_numpy():
    sizes = arithmetic.expand_block_sizes(43, [[1, 5]])
    rng = np.random.default_rng(7)  # one stream for pattern and values
    mine = reference.draw_blocks(rng, rng, sizes, sizes, 0.4, "float64")
    theirs = dt.to_dense(dt.make_random_matrix(
        "A", sizes, sizes, dtype="float64", occupation=0.4,
        rng=np.random.default_rng(7)))
    dense = np.zeros((43, 43))
    off = np.concatenate([[0], np.cumsum(sizes)])
    for e in range(mine.nblks):
        r, c = mine.rows[e], mine.cols[e]
        dense[off[r]:off[r + 1], off[c]:off[c + 1]] = mine.block(e)
    assert np.array_equal(dense, theirs)
    assert sum(len(r) for r, _, _ in mine.by_shape()) == mine.nblks
    rows = reference.sample_block_rows(sizes, 7)
    assert rows[0] == 0 and rows[-1] == len(sizes) - 1
    assert len(rows) == reference.N_SAMPLE_ROWS
    want = reference.product_rows(mine, mine, rows)
    full = dense @ dense
    for r in rows:
        assert np.allclose(want[r], full[off[r]:off[r + 1]], atol=1e-12)
    good = reference.compare_rows(want, want, 1e-14)
    assert good["ok"] and good["rel_err"] == 0.0
    bad = {r: v + (1e-3 if r == rows[1] else 0) for r, v in want.items()}
    assert not reference.compare_rows(want, bad, 1e-14)["ok"]


@pytest.mark.parametrize("total,pattern", [
    (10000, [[1, 23]]),                       # the north star: 23 and 18
    (5000, [[1, 5], [1, 13], [1, 23]]),       # mixed_blocks.perf
    (10000, [[1, 5], [1, 13], [1, 23]]),      # ... scaled, as PERF.md asks
    (20000, [[1, 5], [1, 13], [1, 23]]),
    (120, [[2, 7], [1, 3]]),
], ids=lambda v: str(v).replace(" ", ""))
def test_reference_rows_reach_every_row_block_size_for_any_seed(
        total, pattern):
    """A panel of the reference spans every block column, so a sampled
    row of every row-block size holds every (m, n) bin of C to NumPy,
    whatever the seed: no kernel family can break unseen."""
    sizes = arithmetic.expand_block_sizes(total, pattern)
    every = set(sizes.tolist())
    for seed in range(200):
        rows = reference.sample_block_rows(sizes, seed)
        assert rows == sorted(set(rows)) and 0 in rows
        assert len(sizes) - 1 in rows  # the ragged one
        assert {int(sizes[r]) for r in rows} == every, seed
        assert len(rows) >= min(reference.N_SAMPLE_ROWS, len(sizes))
    assert reference.sample_block_rows(sizes, 3) == \
        reference.sample_block_rows(sizes, 3)
    assert len({tuple(reference.sample_block_rows(sizes, s))
                for s in range(20)}) > 1 or len(sizes) <= 8


# ---------------------------------------------- BENCHMARK.json's limits
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert 1 <= len(SPEC["paths"]) <= 16
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
            assert os.path.exists(os.path.join(REPO, word))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in SPEC["workloads"]} == \
        {c["name"] for c in SPEC["configs"]}
    assert "setup_s" in E2E


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    with open(os.path.join(REPO, entry["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] and len(cfg["reduced"]) <= 16
    assert all(NAME.match(k) for k in entry["reduced"])
    assert set(cfg["guarantees"]) == {"reference", "determinism",
                                      "no_failover", "flops"}
    cells = [w for w in SPEC["workloads"] if w["config"] == entry["name"]]
    assert cells and all(w["chips"] == cfg["chips"] for w in cells)
    assert cfg["grid"][0] * cfg["grid"][1] == cfg["chips"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_entry_names_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = harness.Cell(REPO, cell["name"])
    assert hasattr(loaded.generator, "Generator")
    for m in loaded.metrics("per_layer"):
        spec, reducer = loaded.layer(m["name"])
        assert callable(reducer.reduce)
    e2e = {m["name"] for m in loaded.metrics("end_to_end")}
    # every cell reports setup_s and another end-to-end metric, and a
    # per-layer metric only where the metric it moves is reported
    assert e2e == _e2e_of(cell["name"]) and "setup_s" in e2e and len(e2e) >= 2
    assert loaded.metrics("per_layer")
    assert {m["moves"] for m in loaded.metrics("per_layer")} <= e2e


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_entry_and_its_layer_file(metric):
    e2e = metric["name"] in E2E
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert metric["moves"] in E2E
    assert 1 <= len(metric["layer"]) <= 200
    with open(os.path.join(REPO, "benchmark", "layers",
                           metric["name"] + ".json")) as fh:
        spec = json.load(fh)
    for key in ("layer", "unit", "moves", "source"):
        assert spec[key] == metric[key], key
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "reducers", spec["reducer"] + ".py"))


# --------------------------------------------- a trace recorded on a v5e
def test_recorded_tpu_trace_reduces_to_what_a_timeline_gives():
    """`fixtures/tiny_plain_f64.xplane.pb`: three products of
    `northstar.plain_f64` at 43 x 43 on a TPU v5e (`fixtures/tiny.py`),
    device and host planes only.  The busy union is checked against a
    nanosecond timeline, the rest against numbers read off the trace."""
    trace = xplane.load(os.path.join(FIXTURES, "tiny_plain_f64.xplane.pb"),
                        xplane.wanted_line)
    (ordinal, dev), = xplane.device_planes(trace)
    assert ordinal == 0
    assert {ln["name"] for ln in dev["lines"]} == {
        xplane.OPS_LINE, xplane.MODULES_LINE}
    products = [ev for _, evs in xplane.host_spans(trace, ["bench:product"])
                for ev in evs]
    assert len(products) == 3
    w = (min(e[1] for e in products), max(e[1] + e[2] for e in products))
    assert w == (46075630.0, 70055499.0)
    ops = xplane.clip(xplane.line_events(dev, xplane.OPS_LINE), w)
    timeline = np.zeros(int(w[1] - w[0]), bool)
    for _, start, dur in ops:
        timeline[int(start - w[0]):int(start + dur - w[0])] = True
    assert xplane.union_ns(ops) == timeline.sum() == 272859
    gap_ns = xplane.attribute_gaps(xplane.gaps(ops, w), trace, FAMILY)
    assert sum(gap_ns.values()) == (w[1] - w[0]) - 272859
    assert max(gap_ns, key=gap_ns.get) == "dbcsr_tpu:dense_carve"
    sec = xplane.device_seconds
    assert sec(dev, w, ["jit_dot_general*"]) == pytest.approx(37784e-9)
    assert sec(dev, w, ["jit__gather_bin_from_canvas*"]) == \
        pytest.approx(142858e-9)
    assert sec(dev, w) == pytest.approx(272859e-9)  # self times: no double
    assert xplane.span_self_ns(trace, "dbcsr_tpu:dense_carve", FAMILY, w) \
        == [6638759.0, 6269550.0, 5844230.0]
    text = "\n".join(xplane.describe(trace, 3))
    assert "plane '/device:TPU:0'" in text and "line 'XLA Ops'" in text


def test_a_warning_from_the_program_is_a_failover(
        tiny, capsys, monkeypatch, restore_config):
    # the program says some demotions only through a RuntimeWarning
    state = harness.failover_state
    prog = os.path.join(os.path.dirname(dt.__file__), "acc", "smm.py")

    def noisy(monitor):
        import warnings
        warnings.warn_explicit("crosspack kernel failed; falling back",
                               RuntimeWarning, prog, 1)
        return state(monitor)

    monkeypatch.setattr(harness, "failover_state", noisy)
    rc = harness.run_cell(tiny, "northstar.plain_f64", 3, 0.1, False,
                          platform="cpu")
    line, earlier = _last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert any(ln.startswith("BENCH failover_warnings") for ln in earlier)
