"""The cell `mixed10k_filtered.scf_f64` on the CPU, beyond the tiny run
every cell gets in `test_benchmark_harness.py`: the configuration is
`mixed10k`'s shape for shape, a rehearsal at a cut size is held to
NumPy on a row of every block size (5, 13, 23 and the ragged one), a C
with one block off by a part in 1e10 is not `correct`, and the two
metrics the cell brings (`stack_slot_fill`, `stack_grouped_share`, both
on the reducer `counter_share`) read what the launches counted, in a
window whose plans all hit the plan cache, and say nothing where
nothing is counted.  Counts and results only; no number here is a rate.

On the CPU float64 is native and a cut size passes no `S >= 2048`, so
`prepare_stack` groups nothing by itself: the rehearsal that reads the
fill forces `mm_driver="xla_group"` (the CPU suite's way to the grouped
layout); the chip run takes no such option.
"""

import json
import os
import types

import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, xplane
from benchmark.fixtures.tiny import REPO, tiny_checkout

CELL = "mixed10k_filtered.scf_f64"
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
ENTRIES = "dbcsr_tpu_stack_entries_total"
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
LISTED = {m["name"] for m in SPEC["per_layer"]
          if "workloads" not in m or CELL in m["workloads"]}
NEW = {"stack_slot_fill", "stack_grouped_share"}


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("tiny_mixed_f64")))


@pytest.fixture()
def synthetic(monkeypatch):
    """The benchmark's hand-written trace in place of what a CPU run
    cannot record (`conftest.py` hands out its op metadata)."""
    with open(os.path.join(FIXTURES, "synthetic_trace.json")) as fh:
        trace = json.load(fh)
    monkeypatch.setattr(xplane, "load", lambda path, keep=None: trace)
    info = harness._device_info
    monkeypatch.setattr(harness, "_device_info",
                        lambda devs: dict(info(devs), kind="TPU v5 lite"))
    return trace


@pytest.fixture()
def restore_config():
    cfg = dt.get_config()
    prev = (cfg.incremental, cfg.mm_driver)
    yield
    dt.set_config(incremental=prev[0], mm_driver=prev[1])


def _run(tiny, capsys, *, trace, seed=3):
    assert harness.run_cell(tiny, CELL, seed, 0.3, trace,
                            platform="cpu") == 0
    out = capsys.readouterr().out.splitlines()
    return json.loads(out[-1]), out[:-1]


def _logged(earlier, tag):
    return json.loads(next(ln for ln in earlier if ln.startswith(
        f"BENCH {tag} ")).split(" ", 2)[2])


# ------------------------------------------------------------ the files
def test_configuration_is_mixed10k_in_its_stated_dtype_filtered():
    one, new = _config("mixed10k.json"), _config("mixed10k_filtered.json")
    moved = {k for k in set(one) | set(new) if one.get(k) != new.get(k)}
    assert moved == {"name", "source", "reduced_why", "assumed"}
    assert new["name"] == "mixed10k_filtered"
    assert new["reduced"] == ["m", "n", "k"] and len(new["source"]) <= 200
    for word in ("mixed_blocks.perf", "configs[2]", "dreal",
                 "EPS_FILTER 1.0E-7"):
        assert word in new["source"]
    assert new["filter_eps"] == 1e-7 and new["chips"] == 1
    # what the builder set himself is said, under the keys mixed10k has
    assert set(new["assumed"]) == set(one["assumed"])
    assert "every cell of this configuration runs a filtered traffic" \
        in new["assumed"]["filter_eps"]
    assert "5.9e-14 for a float64 cell" in new["guarantees"]["reference"]
    entry = {c["name"]: c for c in SPEC["configs"]}["mixed10k_filtered"]
    assert entry["reduced"] == ["m", "n", "k"]
    cell = {w["name"]: w for w in SPEC["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixed10k_filtered", "scf_f64", 1)
    # the cell brings no traffic file and no generator: the ones there
    traffic = harness.Cell(REPO, CELL).traffic
    assert traffic["generator"] == "repeat_product" and traffic["filter"]
    assert traffic["dtype"] == "float64"
    assert traffic["program_config"] == {"incremental": "off"}


def test_the_new_metrics_list_the_new_cell_alone():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "stack dispatch"
        spec, reducer = harness.Cell(REPO, CELL).layer(name)
        assert spec["reducer"] == "counter_share"
        assert spec["counter"] == ENTRIES
    assert NEW <= LISTED
    assert {"stack_hbm_share", "stack_launch_s", "stack_dot_s",
            "stack_gather_s", "stack_accum_s", "filter_host_s",
            "index_host_s", "launches_per_multiply"} <= LISTED
    assert CELL in {m["name"]: m for m in SPEC["end_to_end"]}[
        "peak_hbm_gib"]["workloads"]


# ---------------------------------------------------------- the rehearsal
def test_rehearsal_is_correct_on_a_row_of_every_block_size(
        tiny, capsys, restore_config):
    line, earlier = _run(tiny, capsys, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    check = _logged(earlier, "check")["0"]
    sizes = arithmetic.expand_block_sizes(120, [[1, 5], [1, 13], [1, 23]])
    ragged = int(sizes[-1])
    assert ragged not in (5, 13, 23)
    assert check["row_block_sizes"] == sorted({5, 13, 23, ragged})
    assert check["ok"] and check["rel_err"] <= check["tol"]
    assert check["tol"] == arithmetic.reference_tolerance(
        "float64", 23, len(sizes))
    assert check["flops_program"] == check["flops_true"] > 0
    operands = _logged(earlier, "operands")
    assert operands["dtype"] == "float64" and operands["filter_eps"] == 1e-7
    # stacks of every block size on every dimension, the ragged one too
    triples = {(m, n, k) for m, n, k, _, _ in _logged(earlier, "stacks")["0"]}
    for dim in range(3):
        assert {t[dim] for t in triples} == {5, 13, 23, ragged}


def test_one_block_of_c_off_by_a_part_in_1e10_is_not_correct(
        tiny, capsys, monkeypatch, restore_config):
    generator = harness.Cell(tiny, CELL).generator.Generator
    start = generator.start

    def off(self, product):
        c, flops = start(self, product)
        # the first stored block of C lies in block row 0, which every
        # seed samples
        cbin, slot = int(c.ent_bin[0]), int(c.ent_slot[0])
        data = c.bins[cbin].data
        c.bins[cbin].data = data.at[slot].multiply(1.0 + 1e-10)
        return c, flops

    monkeypatch.setattr(generator, "start", off)
    line, earlier = _run(tiny, capsys, trace=False)
    assert line["correct"] is False and line["failed"] == 0
    check = _logged(earlier, "check")["0"]
    assert check["tol"] < check["rel_err"] < 1e-9


# ------------------------------------------------- the traced rehearsal
def _rollup_slots():
    from dbcsr_tpu.core import stats

    got = stats.driver_rollup().get("xla_group", {})
    return got.get("slots_live", 0), got.get("slots_launched", 0)


def test_traced_rehearsal_reads_fill_and_share_from_the_launches(
        tiny, synthetic, capsys, restore_config):
    """Every span grouped (forced, see the module's text): the window's
    plans all hit the plan cache, and the fill the launches counted is
    the fill of the planned tiles."""
    from dbcsr_tpu.obs import metrics

    dt.set_config(mm_driver="xla_group")
    live0, launched0 = _rollup_slots()
    hits0 = dict((lab["result"], v) for lab, v in metrics.counter_items(
        "dbcsr_tpu_plan_cache_total"))
    line, earlier = _run(tiny, capsys, trace=True, seed=5)
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no multiply_filter span in the hand-written trace
    assert set(got) == LISTED - {"filter_host_s"}
    # the plans were made once, in set-up; the window launched them again
    live, launched = (x - x0 for x, x0 in zip(_rollup_slots(),
                                              (live0, launched0)))
    assert 0 < live < launched
    assert got["stack_slot_fill"] == pytest.approx(100.0 * live / launched)
    assert got["stack_grouped_share"] == 100.0
    hits = dict((lab["result"], v) for lab, v in metrics.counter_items(
        "dbcsr_tpu_plan_cache_total"))
    n_products = len(_logged(earlier, "warmup")) + line["attempted"]
    assert hits.get("miss", 0) - hits0.get("miss", 0) == 1
    assert hits.get("hit", 0) - hits0.get("hit", 0) == n_products - 1
    # the earlier lines: the fill of every (m, n, k), the share by driver
    fill = _logged(earlier, "stack_slot_fill")
    assert fill["by"] == "mnk" and "5x13x23" in fill["series"]
    triples = {f"{m}x{n}x{k}" for m, n, k, _, _ in
               _logged(earlier, "stacks")["0"]}
    assert set(fill["series"]) == triples
    for row in fill["series"].values():
        assert 0 < row["part"] <= row["whole"]
        assert row["share"] == pytest.approx(100 * row["part"] / row["whole"])
    entries = sum(e for _, _, _, e, _ in _logged(earlier, "stacks")["0"])
    assert sum(r["part"] for r in fill["series"].values()) == \
        entries * line["attempted"]
    share = _logged(earlier, "stack_grouped_share")
    assert share["by"] == "driver" and set(share["series"]) == {"xla_group"}
    assert got["stack_hbm_share"] > 0 and got["compiles_in_window"] == 0


def test_where_no_device_launch_is_counted_both_say_nothing(
        tiny, synthetic, capsys, restore_config):
    """The CPU's own choice: float64 is native here and the stacks go
    to the native host driver, which launches nothing on a device and
    counts no entry: a share of nothing is left out, not reported as 0."""
    line, earlier = _run(tiny, capsys, trace=True, seed=6)
    assert line["correct"] is True
    assert set(line["metrics"]) == LISTED - {"filter_host_s"} - NEW
    drivers = _logged(earlier, "driver_stacks")
    assert drivers and "xla_group" not in drivers


def test_a_program_without_the_counter_says_nothing(
        tiny, synthetic, capsys, monkeypatch, restore_config):
    """The parent of this PR counts no `dbcsr_tpu_stack_entries_total`:
    both readers return nothing, and the line has every other metric."""
    read = harness.read_counter
    monkeypatch.setattr(
        harness, "read_counter",
        lambda name, monitor: [] if name == ENTRIES else read(name, monitor))
    line, earlier = _run(tiny, capsys, trace=True, seed=7)
    assert line["correct"] is True
    assert set(line["metrics"]) == LISTED - {"filter_host_s"} - NEW
    assert not any(ln.startswith(("BENCH stack_slot_fill",
                                  "BENCH stack_grouped_share"))
                   for ln in earlier)


# --------------------------------------------------- the reducer, by hand
def _series(**by):
    """{"xla_group/5x5x5/live": 3, ...} as counter items."""
    items = []
    for key, v in by.items():
        driver, mnk, kind = key.split("/")
        items.append(({"driver": driver, "mnk": mnk, "kind": kind}, float(v)))
    return items


def _ctx(before, after, spec):
    logged = []
    run = types.SimpleNamespace(counters_before={ENTRIES: before},
                                counters_after={ENTRIES: after})
    return types.SimpleNamespace(
        run=run, layers={"a_share": (spec, None)},
        log=lambda tag, obj: logged.append((tag, obj))), logged


def test_counter_share_by_hand():
    reducer = harness._load_code(os.path.join(
        REPO, "benchmark", "reducers", "counter_share.py"))
    before = _series(**{"xla_group/5x5x5/live": 100,
                        "xla_group/5x5x5/pad": 60,
                        "xla/5x5x19/live": 10, "xla/5x5x19/pad": 6})
    after = _series(**{"xla_group/5x5x5/live": 400,
                       "xla_group/5x5x5/pad": 160,
                       "xla_group/23x23x23/live": 50,
                       "xla_group/23x23x23/pad": 50,
                       "xla/5x5x19/live": 30, "xla/5x5x19/pad": 18,
                       "host/1x1x1/live": 0})
    fill = {"counter": ENTRIES, "scale": 100, "log_by": "mnk",
            "labels": {"driver": "xla_group", "kind": "live"},
            "of_labels": {"driver": "xla_group"}}
    ctx, logged = _ctx(before, after, fill)
    # live 300 + 50 of launched 300 + 100 + 50 + 50
    assert reducer.reduce(fill, ctx) == pytest.approx(100 * 350 / 500)
    (tag, line), = logged
    assert tag == "a_share" and line["by"] == "mnk"
    assert line["series"] == {
        "23x23x23": {"part": 50.0, "whole": 100.0, "share": 50.0},
        "5x5x5": {"part": 300.0, "whole": 400.0, "share": 75.0}}
    share = {"counter": ENTRIES, "scale": 100, "log_by": "driver",
             "labels": {"driver": "xla_group", "kind": "live"},
             "of_labels": {"kind": "live"}}
    ctx, logged = _ctx(before, after, share)
    # 350 live on xla_group of 350 + 20 on every driver
    assert reducer.reduce(share, ctx) == pytest.approx(100 * 350 / 370)
    assert set(logged[0][1]["series"]) == {"xla", "xla_group"}  # host: 0
    assert logged[0][1]["series"]["xla"]["share"] == 0.0
    # no scale, no log line, every series the whole
    plain = {"counter": ENTRIES, "labels": {"kind": "live"}}
    ctx, logged = _ctx(before, after, plain)
    assert reducer.reduce(plain, ctx) == pytest.approx(370 / 532)
    assert logged == []
    # a window in which the whole stood still, or a counter that is not
    # there: nothing, not 0 and no line
    ctx, logged = _ctx(after, after, fill)
    assert reducer.reduce(fill, ctx) is None and logged == []
    ctx, logged = _ctx([], [], share)
    assert reducer.reduce(share, ctx) is None and logged == []
    ctx.run.counters_before = ctx.run.counters_after = {}
    assert reducer.reduce(share, ctx) is None
