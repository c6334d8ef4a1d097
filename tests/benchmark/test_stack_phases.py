"""The op-metadata reader and the scope reducer, on traces kept with
the benchmark: one hand-written, two recorded on a TPU v5e.  No number
here is a rate of this machine.
"""

import gzip
import json
import os
import types

import pytest

from benchmark import harness, opmeta, xplane
from benchmark.fixtures.tiny import REPO

FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
PLAIN = os.path.join(FIXTURES, "tiny_plain_f64.xplane.pb")
SCOPED = os.path.join(FIXTURES, "tiny_scf_f64_scoped.xplane.pb.gz")
STACK_MODULES = ["jit_fused*", "jit__stack_*"]
PHASE_METRICS = ("stack_gather_s", "stack_dot_s", "stack_accum_s")


def _reducer(name):
    return harness._load_code(os.path.join(
        REPO, "benchmark", "reducers", name + ".py"))


def _layer(metric):
    with open(os.path.join(REPO, "benchmark", "layers",
                           metric + ".json")) as fh:
        return json.load(fh)


def _ctx(tmp_path, trace=None, *, recorded=b"", devices=1):
    """What the harness hands a reducer: a trace in `xplane.load`'s form
    (the recorded file's own where none is given) and, for the reducer
    that reads the file itself, the file where a traced run leaves it."""
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "kept.xplane.pb").write_bytes(recorded)
    if trace is None:
        trace = xplane.load(str(run_dir / "kept.xplane.pb"),
                            xplane.wanted_line)
    spans = [ev for _, evs in xplane.host_spans(trace, ["bench:product"])
             for ev in evs]
    lines = []
    run = types.SimpleNamespace(
        trace=trace, records=[{}] * len(spans),
        trace_window=(min(e[1] for e in spans),
                      max(e[1] + e[2] for e in spans)))
    return types.SimpleNamespace(
        run=run, xplane=xplane, devices=list(range(devices)),
        family=harness.HOST_SPAN_FAMILY,
        trace_dir=str(tmp_path / "trace"),
        log=lambda tag, obj: lines.append((tag, obj))), lines


def _recorded(tmp_path, path):
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        return _ctx(tmp_path, recorded=fh.read())


# ------------------------------------------------------------- the reader
@pytest.mark.parametrize("path", [PLAIN, SCOPED], ids=["plain", "scoped"])
def test_opmeta_gives_profiledata_events_with_their_metadata(tmp_path, path):
    (ctx, _) = _recorded(tmp_path, path)
    file = xplane.find_xplane(ctx.trace_dir)
    ops = opmeta.device_ops(file)
    planes = xplane.device_planes(ctx.run.trace)
    assert sorted(ops) == [plane["name"] for _, plane in planes]
    for _, plane in planes:
        mine = ops[plane["name"]]
        assert len(mine) > 500
        # same events in the same order, same names, same times
        assert [ev[:3] for ev in mine] == xplane.line_events(
            plane, xplane.OPS_LINE)
        assert all(isinstance(ev[3], str) and isinstance(ev[4], str)
                   and isinstance(ev[5], int) for ev in mine)
    paths = {ev[3] for ev in mine}
    sources = {ev[4] for ev in mine}
    if path == PLAIN:
        assert "jit(_gather_bin_from_canvas)/gather:" in paths
        assert "/root/repo/dbcsr_tpu/mm/multiply.py:687" in sources
    else:
        assert any("/stk_loop/while/body/closed_call/stk_dot/" in p
                   and p.startswith("jit(fused_superstack)/span0.")
                   for p in paths)
    assert any(ev[5] > 0 for ev in mine)


def test_opmeta_joins_by_metadata_id_not_by_name(tmp_path):
    (ctx, _) = _recorded(tmp_path, PLAIN)
    ops, = opmeta.device_ops(xplane.find_xplane(ctx.trace_dir)).values()
    # two programs each hold an op of one label with its own scope path
    by_label = {}
    for name, _, _, tf_op, _, _ in ops:
        by_label.setdefault(xplane.op_label(name), set()).add(tf_op)
    assert any(len(paths) > 1 for paths in by_label.values())


def test_a_file_that_is_no_xplane_is_an_error(tmp_path):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0f\x01\x02")
    with pytest.raises(ValueError, match="not an xplane"):
        opmeta.device_ops(str(bad))


# ------------------------------------------------------------ the reducer
def test_scope_time_on_the_recorded_dense_trace_by_hand(tmp_path):
    ctx, lines = _recorded(tmp_path, PLAIN)
    by_scope = _reducer("device_time_by_scope")
    spec = {"modules": ["jit__gather_bin_from_canvas*"],
            "scopes": ["*/gather*"]}
    # no stk_* scope in a program of the dense route: nothing to report
    assert by_scope.reduce(dict(spec, log="stack_phases"), ctx) is None
    # the same ops, summed as `device_time_by_module` sums them
    ops, = opmeta.device_ops(xplane.find_xplane(ctx.trace_dir)).values()
    labels = {xplane.op_label(ev[0]) for ev in ops
              if xplane.matches(ev[3], spec["scopes"])
              and ev[3].startswith("jit(_gather_bin_from_canvas)")}
    assert labels
    (_, plane), = xplane.device_planes(ctx.run.trace)
    want = xplane.device_seconds(plane, ctx.run.trace_window,
                                 spec["modules"], sorted(labels))
    rows, = by_scope._rows(ctx)
    got = 1e-9 * sum(
        self_ns for mod, ev, self_ns in rows
        if xplane.matches(mod, spec["modules"])
        and xplane.matches(ev[3], spec["scopes"]))
    assert got == pytest.approx(want) and got > 0
    # every op of the window is there once, with its self time
    assert 1e-9 * sum(r[2] for r in rows) == pytest.approx(272859e-9)


def test_a_scopeless_executable_reads_none_and_says_so_loudly(tmp_path):
    ctx, lines = _recorded(tmp_path, PLAIN)
    by_scope = _reducer("device_time_by_scope")
    for metric in PHASE_METRICS:
        spec = dict(_layer(metric), modules=["jit_dot_general*"])
        assert by_scope.reduce(spec, ctx) is None
    loud = [obj for tag, obj in lines if tag == "stack_phases"]
    assert len(loud) == 1  # once a run, however many metrics ask
    text = loud[0]["NO_SCOPE"]
    assert "COMPILE CACHE" in text and "jit_dot_general*" in text
    rest = text.replace("jit_dot_general*", "").replace("stk_*", "")
    assert rest == rest.upper()  # in capitals, but for the names
    assert loud[0]["modules_seen"] == ["jit_dot_general"]
    # modules that never ran are no finding: silent
    assert by_scope.reduce(dict(_layer("stack_dot_s"),
                                modules=["jit_never*"]), ctx) is None
    assert len([1 for tag, _ in lines if tag == "stack_phases"]) == 1


def test_phases_of_the_recorded_filtered_trace_add_up(tmp_path):
    """`fixtures/tiny_scf_f64_scoped.xplane.pb.gz`: three products of
    `northstar.scf_f64` at 43 x 43 on a TPU v5e with this PR's scopes
    (`fixtures/tiny.py`, then `fixtures/cut.py`)."""
    ctx, lines = _recorded(tmp_path, SCOPED)
    by_scope = _reducer("device_time_by_scope")
    values = {m: by_scope.reduce(_layer(m), ctx) for m in PHASE_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    launch = _reducer("device_time_by_module").reduce(
        _layer("stack_launch_s"), ctx)
    (phases,) = [obj for tag, obj in lines if tag == "stack_phases"]
    per = phases["seconds_per_product"]
    for metric, scope in zip(PHASE_METRICS,
                             ("stk_gather", "stk_dot", "stk_accum")):
        assert per[scope] == pytest.approx(values[metric])
    assert set(per) <= {"stk_gather", "stk_dot", "stk_accum", "stk_pad",
                        "stk_loop", "unscoped"}
    assert sum(per.values()) == pytest.approx(launch, rel=0.01)
    assert phases["sum"] == pytest.approx(sum(per.values()))
    assert sum(phases["by_span"].values()) == pytest.approx(phases["sum"])
    assert any(name.startswith("span0.") for name in phases["by_span"])
    assert "estimate" in phases["note"]
    assert len(phases["unscoped_top_sources"]) <= 5
    # the file was read once for the three metrics
    assert len([1 for tag, _ in lines if tag == "opmeta"]) == 1
    # the wait inside the filter has a span of its own now
    spans = _reducer("host_span_self")
    wait = spans.reduce(_layer("filter_norms_wait_s"), ctx)
    rest = spans.reduce(_layer("filter_host_s"), ctx)
    assert wait > 0 and rest > 0
    total = sum(ev[2] for _, evs in xplane.host_spans(
        ctx.run.trace, ["dbcsr_tpu:multiply_filter"]) for ev in evs)
    assert (wait + rest) * len(ctx.run.records) == pytest.approx(
        total * 1e-9)


def test_phases_of_the_hand_written_trace_by_hand(tmp_path,
                                                  synthetic_opmeta):
    with open(os.path.join(FIXTURES, "synthetic_trace.json")) as fh:
        trace = json.load(fh)
    by_scope = _reducer("device_time_by_scope")
    ctx, lines = _ctx(tmp_path, trace, devices=2)
    got = {m: by_scope.reduce(_layer(m), ctx) for m in PHASE_METRICS}
    # two products; gather and dot on device 0, accum on device 1: each
    # metric is the device's that spends most in its scope
    assert got == {"stack_gather_s": pytest.approx(250e-9),
                   "stack_dot_s": pytest.approx(200e-9),
                   "stack_accum_s": pytest.approx(500e-9)}
    (phases,) = [obj for tag, obj in lines if tag == "stack_phases"]
    # the line is device 0's, which spends most in the modules: the
    # while's body is counted once (its self time is the loop's)
    assert phases["seconds_per_product"] == {
        "unscoped": pytest.approx(500e-9), "stk_loop": pytest.approx(300e-9),
        "stk_gather": pytest.approx(250e-9), "stk_dot": pytest.approx(200e-9)}
    assert phases["sum"] == pytest.approx(1250e-9)
    assert phases["by_span"] == {
        "span0.xla_group.5x5x5": pytest.approx(1250e-9)}
    assert phases["unscoped_top_sources"] == [
        ["/repo/dbcsr_tpu/acc/smm.py:215", pytest.approx(500e-9)]]
    assert phases["xla_gbytes_per_s"]["stk_gather"] == pytest.approx(2.0)
    # one device asked for: device 1's accum is not the cell's
    ctx, _ = _ctx(tmp_path / "one", trace, devices=1)
    assert by_scope.reduce(_layer("stack_accum_s"), ctx) == 0.0


def test_every_layer_file_names_a_reducer_that_is_there():
    layers = os.path.join(REPO, "benchmark", "layers")
    for name in sorted(os.listdir(layers)):
        spec = _layer(name[:-5])
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "reducers", spec["reducer"] + ".py")), name
