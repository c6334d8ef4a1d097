"""The set-up metrics of PR 34: the reducer `counter_at_window_start` by
hand, their entries in `BENCHMARK.json`, and a rehearsal of a traced
cell at a few blocks in a process of its own, as the driver runs one.
A CPU run: counts and host seconds, no device number."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness
from benchmark.fixtures.tiny import REPO

ALL_CELLS = ["setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
             "setup_programs", "setup_before_init_s"]
F32_CELLS = {"setup_validate_s": ["mixed10k.plain_f32",
                                  "northstar.plain_f32"]}
SECONDS = "dbcsr_tpu_compile_seconds_total"
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)


def _reducer():
    return harness._load_code(os.path.join(
        REPO, "benchmark", "reducers", "counter_at_window_start.py"))


def _ctx(before, layers=None):
    logged = []
    ctx = types.SimpleNamespace(
        run=types.SimpleNamespace(counters_before=before),
        layers=layers or {}, log=lambda tag, obj: logged.append((tag, obj)))
    return ctx, logged


SERIES = [({"stage": "trace", "fn": "f", "phase": "a"}, 1.0),
          ({"stage": "lower", "fn": "f", "phase": "a"}, 2.0),
          ({"stage": "compile", "fn": "f", "phase": "a"}, 4.0),
          ({"stage": "compile", "fn": "g", "phase": "b"}, 8.0),
          ({"stage": "cache_load", "fn": "g", "phase": ""}, 16.0)]


@pytest.mark.parametrize("labels, want", [
    ({"stage": ["trace", "lower"]}, 3.0),
    ({"stage": "compile"}, 12.0),
    ({"stage": "compile", "fn": ["g", "h"]}, 8.0),
    ({}, 31.0),
    ({"stage": "none_such"}, 0.0),
])
def test_reducer_sums_the_matching_series_of_the_window_start_snapshot(
        labels, want):
    ctx, logged = _ctx({SECONDS: SERIES})
    spec = {"counter": SECONDS, "labels": labels, "scale": 0.5}
    assert _reducer().reduce(spec, ctx) == want * 0.5
    assert not logged


def test_reducer_reads_0_where_the_program_has_no_such_counter():
    # the parent of PR 34, or a snapshot that does not hold the counter
    for before in ({}, {SECONDS: []}):
        ctx, _ = _ctx(before)
        assert _reducer().reduce({"counter": SECONDS,
                                  "labels": {"stage": "compile"}}, ctx) == 0.0


def test_reducer_logs_the_top_series_under_the_metric_name():
    spec = {"counter": SECONDS, "labels": {"stage": "compile"},
            "log_by": ["fn", "phase"]}
    ctx, logged = _ctx({SECONDS: SERIES},
                       layers={"other": ({"counter": SECONDS}, None),
                               "setup_compile_s": (spec, None)})
    assert _reducer().reduce(spec, ctx) == 12.0
    assert logged == [("setup_compile_s", {
        "series": {"fn=g,phase=b": 8.0, "fn=f,phase=a": 4.0},
        "by": {"fn": {"g": 8.0, "f": 4.0}, "phase": {"b": 8.0, "a": 4.0}}})]


def test_reducer_frees_a_label_it_logs_by_and_keeps_twelve():
    spans = [({"span": f"s{i:02d}", "kind": kind}, float(i + 1))
             for i in range(20) for kind in ("self", "total")]
    spec = {"counter": "spans", "labels": {"span": "s03", "kind": "total"},
            "log_by": ["span"]}
    ctx, logged = _ctx({"spans": spans})
    assert _reducer().reduce(spec, ctx) == 4.0
    (tag, line), = logged
    assert tag == "spans"  # no layer of that spec: the counter's name
    # totals only (kind is still held), the twelve largest, one label: no
    # second table
    assert line == {"series": {f"span=s{i:02d}": float(i + 1)
                               for i in range(19, 7, -1)}}


@pytest.mark.parametrize("name", ALL_CELLS + list(F32_CELLS))
def test_entry_and_layer_file_of_a_setup_metric(name):
    entry = {m["name"]: m for m in SPEC["per_layer"]}[name]
    assert entry["layer"] == "set-up" and entry["moves"] == "setup_s"
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert entry.get("workloads") == F32_CELLS.get(name)
    with open(os.path.join(REPO, "benchmark", "layers",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reducer"] == "counter_at_window_start"
    assert spec["counter"].startswith("dbcsr_tpu_") and spec["what"]
    # appended: nothing that was there moved down the list
    names = [m["name"] for m in SPEC["per_layer"]]
    assert names.index(name) > names.index("plan_cache_misses")


REHEARSAL = """
import json, os, sys
sys.path[:0] = [{repo!r}, os.path.join({repo!r}, "tests")]
import conftest  # the CPU platform, as the tests have it
from benchmark import harness, opmeta, xplane
from benchmark.fixtures.tiny import tiny_checkout
fixtures = os.path.join({repo!r}, "benchmark", "fixtures")
with open(os.path.join(fixtures, "synthetic_trace.json")) as fh:
    trace = json.load(fh)
with open(os.path.join(fixtures, "synthetic_opmeta.json")) as fh:
    meta = {{k: v for k, v in json.load(fh).items() if not k.startswith("_")}}
# a CPU trace has no device plane: the hand-written one, as in
# test_benchmark_harness.py
xplane.load = lambda path, keep=None: trace
opmeta.device_ops = lambda path: meta
info = harness._device_info
harness._device_info = lambda devs: dict(info(devs), kind="TPU v5 lite")
sys.exit(harness.run_cell(tiny_checkout({dst!r}), {cell!r}, 2147483659, 0.3,
                          True, platform="cpu"))
"""


@pytest.mark.parametrize("cell", ["northstar.scf_f64", "northstar.plain_f32"])
def test_traced_tiny_cell_in_its_own_process_reports_the_setup_metrics(
        cell, tmp_path):
    out = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(repo=REPO, dst=str(tmp_path), cell=cell)],
        cwd=str(tmp_path), timeout=600, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    bench = {}
    for ln in lines[:-1]:
        if ln.startswith("BENCH "):
            _, tag, obj = ln.split(" ", 2)
            bench[tag] = json.loads(obj)
    line = json.loads(lines[-1])
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    want = ALL_CELLS + [m for m, cells in F32_CELLS.items() if cell in cells]
    assert set(want) <= set(got)
    # what JAX did, by the program's listener and by the harness's own
    assert got["setup_programs"] == \
        bench["compile_cache"]["jax_backend_compiles"] > 0
    assert bench["setup_programs"]["by"]["stage"] == {
        "compile": got["setup_programs"]}  # CPU: nothing under 1 s is cached
    assert set(bench["setup_programs"]["by"]) == {"stage", "fn", "phase"}
    assert sum(bench["setup_programs"]["by"]["fn"].values()) <= \
        got["setup_programs"]
    assert got["setup_cache_load_s"] == 0 and got["setup_compile_s"] > 0
    assert got["setup_trace_lower_s"] > 0
    setup_s = bench["end_to_end_of_traced_run_not_reported"]["setup_s"]
    stages = (got["setup_trace_lower_s"] + got["setup_compile_s"]
              + got["setup_cache_load_s"])
    assert stages < setup_s
    # the harness counts set-up from run_cell's start here, the region
    # from the process's: the imports above lie in the one, not the other
    assert got["setup_before_init_s"] > 0
    spans = bench["setup_before_init_s"]["series"]
    assert spans["span=before_init"] == got["setup_before_init_s"]
    assert {"span=import", "span=matrix_finalize", "span=multiply"} <= \
        set(spans)
    assert spans["span=import"] < spans["span=before_init"]
