"""What JAX reports of its own compiles, booked into the registry
(`obs.metrics.listen_to_compiles`), the timer table read through the
same registry, and the set-up spans.  Counts and seconds of the host:
nothing here is a device number."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

import dbcsr_tpu as dt
from dbcsr_tpu.core import lib, timings
from dbcsr_tpu.obs import metrics

SECONDS, PROGRAMS = metrics.COMPILE_SECONDS, metrics.COMPILE_PROGRAMS
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture(autouse=True)
def listening():
    dt.init_lib()
    metrics.listen_to_compiles()  # a registry another test reset stays heard


def _series(name, **want):
    """{(stage, fn, phase) or labels tuple: value} of the matching series."""
    return {tuple(sorted(lab.items())): v
            for lab, v in metrics.counter_items(name)
            if all(lab.get(k) == v_ for k, v_ in want.items())}


def _total(name, **want):
    return sum(_series(name, **want).values())


def _fresh(name, inner=None):
    """A jitted function nothing has traced or compiled before."""
    def body(x):
        y = jnp.cos(x) * 3.0 + 1.0
        return y if inner is None else inner(y) + y
    body.__name__ = name
    return jax.jit(body)


def test_fresh_function_books_trace_lower_and_one_compile_to_fn_and_phase():
    f = _fresh("events_fresh_fn")
    with timings.timed("x"):
        f(jnp.arange(7.0)).block_until_ready()
    for stage in ("trace", "lower", "compile"):
        got = _series(SECONDS, fn="events_fresh_fn", stage=stage)
        # one name from all three stages: tracing says `f`, lowering and
        # the backend say `jit(f)`
        assert list(got) == [(("fn", "events_fresh_fn"), ("phase", "x"),
                              ("stage", stage))], stage
        assert next(iter(got.values())) > 0
    assert _series(PROGRAMS, fn="events_fresh_fn") == {
        (("fn", "events_fresh_fn"), ("phase", "x"), ("stage", "compile")): 1}
    assert not _series(SECONDS, fn="events_fresh_fn", stage="cache_load")


def test_second_call_books_nothing():
    f = _fresh("events_twice_fn")
    f(jnp.arange(5.0)).block_until_ready()
    before = (metrics.counter_items(SECONDS), metrics.counter_items(PROGRAMS))
    with timings.timed("x"):
        f(jnp.arange(5.0)).block_until_ready()
    assert (metrics.counter_items(SECONDS),
            metrics.counter_items(PROGRAMS)) == before


def test_no_open_span_is_the_empty_phase():
    assert not timings._stack
    _fresh("events_no_phase_fn")(jnp.arange(3.0)).block_until_ready()
    assert _series(PROGRAMS, fn="events_no_phase_fn") == {
        (("fn", "events_no_phase_fn"), ("phase", ""), ("stage", "compile")): 1}


def test_init_lib_twice_installs_one_listener(monkeypatch):
    monkeypatch.setattr(lib, "_initialized", False)
    dt.init_lib()
    monkeypatch.setattr(lib, "_initialized", False)
    dt.init_lib()
    metrics.listen_to_compiles()
    assert jax_monitoring.get_event_duration_listeners().count(
        metrics._on_compile_duration) == 1
    assert jax_monitoring.get_scalar_listeners().count(
        metrics._on_compile_start) == 1


def test_backend_event_after_a_retrieval_is_a_cache_load_fed_by_hand():
    """The two events as `compiler.compile_or_get_cached` fires them on a
    hit: the retrieval (no name) inside the backend event."""
    for loaded in (True, False):
        jax_monitoring.record_scalar(BACKEND, time.time(),
                                     fun_name="jit(events_fed_fn)")
        if loaded:
            jax_monitoring.record_event_duration_secs(RETRIEVAL, 0.125)
        with timings.timed("fed"):
            jax_monitoring.record_event_duration_secs(
                BACKEND, 0.5, fun_name="jit(events_fed_fn)")
    # the flag of the hit did not leak into the next backend event
    assert _series(SECONDS, fn="events_fed_fn") == {
        (("fn", "events_fed_fn"), ("phase", "fed"), ("stage", "cache_load")):
            0.5,
        (("fn", "events_fed_fn"), ("phase", "fed"), ("stage", "compile")):
            0.5}
    assert sorted(_series(PROGRAMS, fn="events_fed_fn").values()) == [1, 1]


def test_persistent_cache_hit_is_a_cache_load_and_not_a_compile(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    options = {"jax_compilation_cache_dir": str(tmp_path),
               "jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": -1}
    held = {k: getattr(jax.config, k) for k in options}
    hits = []

    def count_hits(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax_monitoring.register_event_listener(count_hits)
    try:
        for k, v in options.items():
            jax.config.update(k, v)
        cc.reset_cache()

        def events_cached_fn(x):
            return jnp.tanh(x) * 5.0 - 2.0

        jax.jit(events_cached_fn)(jnp.arange(11.0)).block_until_ready()
        assert _total(PROGRAMS, fn="events_cached_fn", stage="compile") == 1
        jax.clear_caches()  # the in-memory executables, not the directory
        jax.jit(events_cached_fn)(jnp.arange(11.0)).block_until_ready()
    finally:
        jax_monitoring.unregister_event_listener(count_hits)
        for k, v in held.items():
            jax.config.update(k, v)
        cc.reset_cache()
    if not hits:
        pytest.skip("this backend served no persistent-cache hit")
    assert _total(PROGRAMS, fn="events_cached_fn", stage="cache_load") == 1
    assert _total(PROGRAMS, fn="events_cached_fn", stage="compile") == 1
    assert _total(SECONDS, fn="events_cached_fn", stage="cache_load") > 0


def test_stages_are_exclusive_and_programs_are_the_backend_events():
    """A chain of jitted callees, each traced inside its caller: an
    independent listener sees every raw event; the counters hold each
    second once."""
    raw = {TRACE: [], LOWER: [], BACKEND: []}

    def independent(event, secs, **kw):
        if event in raw:
            raw[event].append((kw.get("fun_name", ""), secs))

    fn = None
    for depth in reversed(range(6)):
        fn = _fresh(f"events_nest{depth}_fn", fn)
    x = jnp.arange(9.0)  # made here: the one top-level trace below is fn's
    before = {s: _total(SECONDS, stage=s)
              for s in ("trace", "lower", "compile", "cache_load")}
    programs = _total(PROGRAMS)
    jax_monitoring.register_event_duration_secs_listener(independent)
    t0 = time.perf_counter()
    try:
        fn(x).block_until_ready()
    finally:
        wall = time.perf_counter() - t0
        jax_monitoring.unregister_event_duration_listener(independent)
    moved = {s: _total(SECONDS, stage=s) - before[s] for s in before}
    assert _total(PROGRAMS) - programs == len(raw[BACKEND]) >= 1
    # every callee was traced inside the outermost trace event: summed
    # raw, the nest counts its inside again at every level
    outermost = dict(raw[TRACE])["events_nest0_fn"]
    assert moved["trace"] == pytest.approx(outermost, rel=1e-6)
    assert sum(s for _, s in raw[TRACE]) > outermost
    assert moved["lower"] == pytest.approx(sum(s for _, s in raw[LOWER]))
    assert moved["compile"] + moved["cache_load"] == pytest.approx(
        sum(s for _, s in raw[BACKEND]))
    assert 0 < sum(moved.values()) <= wall
    # and each level's own seconds went to its own name
    assert all(_total(SECONDS, fn=f"events_nest{d}_fn", stage="trace") > 0
               for d in range(6))


def test_timer_view_gives_self_total_and_calls_of_a_nested_pair():
    with timings.timed("events_outer"):
        time.sleep(0.02)
        for _ in range(2):
            with timings.timed("events_inner"):
                time.sleep(0.01)
    secs = {(lab["span"], lab["kind"]): v for lab, v in
            metrics.counter_items("dbcsr_tpu_span_seconds_total")}
    calls = {lab["span"]: v for lab, v in
             metrics.counter_items("dbcsr_tpu_span_calls_total")}
    assert calls["events_outer"] == 1 and calls["events_inner"] == 2
    inner, outer = secs["events_inner", "total"], secs["events_outer", "total"]
    assert inner >= 0.02 and outer >= inner + 0.02
    assert secs["events_inner", "self"] == inner
    assert secs["events_outer", "self"] == pytest.approx(outer - inner)
    # a view, not a bump: the registry holds no such counter
    assert "dbcsr_tpu_span_seconds_total" not in metrics._counters


def test_timer_view_is_in_the_exposition_and_the_snapshot():
    with timings.timed("events_shown"):
        pass
    text = metrics.prometheus_text()
    assert "# TYPE dbcsr_tpu_span_seconds_total counter" in text
    assert 'dbcsr_tpu_span_seconds_total{kind="self",span="events_shown"}' \
        in text
    assert 'dbcsr_tpu_span_calls_total{span="events_shown"} 1' in text
    snap = metrics.snapshot()["counters"]
    assert snap["dbcsr_tpu_span_calls_total"]['{"span": "events_shown"}'] == 1
    assert '{"kind": "total", "span": "events_shown"}' in \
        snap["dbcsr_tpu_span_seconds_total"]


def test_compile_counters_are_in_the_exposition():
    _fresh("events_exposed_fn")(jnp.arange(4.0)).block_until_ready()
    text = metrics.prometheus_text()
    assert (f'{PROGRAMS}{{fn="events_exposed_fn",phase="",stage="compile"}} 1'
            in text)
    assert f"# TYPE {SECONDS} counter" in text


def test_init_lib_books_before_init_once_with_import_inside(monkeypatch):
    monkeypatch.setattr(timings, "_stats", {})
    monkeypatch.setattr(lib, "_before_init_booked", False)
    timings.book("import", 0.25)
    for _ in range(2):
        monkeypatch.setattr(lib, "_initialized", False)
        dt.init_lib()
    age = lib._seconds_since_process_start()
    if age is None:
        pytest.skip("no /proc/self/stat here")
    region = timings._stats["before_init"]
    assert region.calls == 1
    assert 0.25 < region.total <= age
    assert region.self_time == pytest.approx(region.total - 0.25)
    assert timings._stats["init_lib"].calls == 2


def test_a_fresh_process_books_import_inside_before_init():
    import json
    import os
    import subprocess
    import sys

    code = (
        "import json, dbcsr_tpu as dt\n"
        "from dbcsr_tpu.obs import metrics\n"
        "dt.init_lib()\n"
        "print(json.dumps(metrics.counter_items("
        "'dbcsr_tpu_span_seconds_total')))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, timeout=300, check=True,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    secs = {(lab["span"], lab["kind"]): v
            for lab, v in json.loads(out.splitlines()[-1])}
    assert 0 < secs["import", "total"] < secs["before_init", "total"]
    assert secs["before_init", "self"] == pytest.approx(
        secs["before_init", "total"] - secs["import", "total"])
    assert ("init_lib", "total") in secs


def test_first_use_kernel_validation_is_a_span_and_a_phase():
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(29)
    m = n = k = 7  # a shape no other test validates
    a = rng.standard_normal((8, m, k)).astype(np.float32)
    b = rng.standard_normal((8, k, n)).astype(np.float32)
    c = np.zeros((6, m, n), np.float32)
    ai, bi = rng.integers(0, 8, 60), rng.integers(0, 8, 60)
    ci = np.sort(rng.integers(0, 6, 60))
    smm._validated_kernels.difference_update(
        {key for key in smm._validated_kernels
         if key[:4] == (m, n, k, "float32")})
    calls = getattr(timings._stats.get("kernel_validate"), "calls", 0)
    programs = _total(PROGRAMS, phase="kernel_validate")
    set_config(mm_driver="pallas", validate_kernels=True)
    try:
        smm.process_stack(c, a, b, ai, bi, ci)
        assert timings._stats["kernel_validate"].calls == calls + 1
        assert _total(PROGRAMS, phase="kernel_validate") > programs
        smm.process_stack(c, a, b, ai, bi, ci)  # validated: no span
        assert timings._stats["kernel_validate"].calls == calls + 1
    finally:
        set_config(mm_driver="auto")


def test_staging_blocks_to_the_device_is_the_span_matrix_finalize():
    calls = getattr(timings._stats.get("matrix_finalize"), "calls", 0)
    mat = dt.create("m", [3, 3], [3, 3], dtype=np.float64)
    mat.finalize()  # nothing staged: no span
    assert getattr(timings._stats.get("matrix_finalize"), "calls", 0) == calls
    mat.put_block(0, 1, np.ones((3, 3)))
    mat.finalize()
    assert timings._stats["matrix_finalize"].calls == calls + 1
    np.testing.assert_array_equal(dt.to_dense(mat)[:3, 3:], np.ones((3, 3)))


def test_building_the_native_index_library_is_the_span_native_build(
        monkeypatch):
    from dbcsr_tpu import native

    built = []
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_fresh", lambda: False)
    # no g++ here: the library this process already has, if it has one
    monkeypatch.setattr(native, "_build",
                        lambda: built.append(1) or (
                            native._SO if os.path.exists(native._SO)
                            else None))
    calls = getattr(timings._stats.get("native_build"), "calls", 0)
    native.get_lib()
    native.get_lib()  # tried: neither a build nor a span
    assert built == [1]
    assert timings._stats["native_build"].calls == calls + 1


def test_a_booked_region_on_another_thread_leaves_the_open_spans_alone():
    """What `matrix_finalize` and `native_build` are booked with: a client
    thread stages blocks while the engine's thread has spans open."""
    import threading

    calls = getattr(timings._stats.get("matrix_finalize"), "calls", 0)
    mat = dt.create("m", [4], [4], dtype=np.float64)
    mat.put_block(0, 0, np.eye(4))
    with timings.timed("events_engine_span"):
        depth = len(timings._stack)
        worker = threading.Thread(target=mat.finalize)
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive()
        assert len(timings._stack) == depth
        assert timings._stack[-1][0] == "events_engine_span"
    assert timings._stats["matrix_finalize"].calls == calls + 1
    assert mat.valid
