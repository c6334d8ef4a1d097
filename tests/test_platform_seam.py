"""Platform-injection seam: the CPU suite asserts TPU-only dispatch
DECISIONS (VERDICT r4 item 5).

The round-4 bug class this guards: auto dispatch selected the
interpret-mode Pallas driver off-TPU (~1000x slowdown masquerading as a
hang, fix f874263) — the branch lived behind `platform != "tpu"` and
was untestable on the CPU suite.  `config.platform_override` now lets
these tests fake the platform for every decision site
(_pallas_supported, the format planner, emulated_dtype_on_tpu /
_stack_r0, _host_smm_available) while execution still follows the real
backend.  Reference analog: the careful-mode dispatch asserts of
`dbcsr_mm_sched.F:295-321`, which stay testable off-GPU.
"""

import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu.core.config import (
    effective_platform,
    get_config,
    set_config,
)

dt.init_lib()


@pytest.fixture
def fake_tpu():
    set_config(platform_override="tpu")
    yield
    set_config(platform_override="")


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = get_config()
    saved = (cfg.mm_driver, cfg.use_pallas, cfg.platform_override)
    yield
    set_config(mm_driver=saved[0], use_pallas=saved[1],
               platform_override=saved[2])


def _stack_arrays(dtype, m=23, n=23, k=23, nblk=64, nseg=32):
    import jax.numpy as jnp

    a = jnp.zeros((nblk, m, k), dtype)
    b = jnp.zeros((nblk, k, n), dtype)
    c = jnp.zeros((nseg, m, n), dtype)
    rng = np.random.default_rng(0)
    S = 4096  # >= 2048 so the emulated-dtype R-tiling branch is live
    ai = rng.integers(0, nblk, S)
    bi = rng.integers(0, nblk, S)
    ci = np.sort(rng.integers(0, nseg, S))
    return c, a, b, ai, bi, ci


def test_effective_platform_default_is_real():
    assert effective_platform() == "cpu"


def test_override_validated():
    with pytest.raises(ValueError):
        set_config(platform_override="gpu")
    assert get_config().platform_override == ""


def test_auto_never_selects_interpret_pallas_off_tpu():
    """The f874263 regression test: on a CPU backend, auto dispatch
    must never pick the Pallas driver (interpret mode, ~1000x)."""
    from dbcsr_tpu.acc.smm import _pallas_supported, prepare_stack

    c, a, b, ai, bi, ci = _stack_arrays(np.float32)
    set_config(mm_driver="auto", use_pallas=True)
    assert not _pallas_supported(get_config(), c, a, b)
    plan = prepare_stack(c, a, b, ai, bi, ci)
    assert not plan.driver.startswith("pallas"), plan.driver


def test_explicit_pallas_force_still_works_off_tpu():
    """Tests/kernel debugging rely on forcing interpret-mode Pallas."""
    from dbcsr_tpu.acc.smm import _pallas_supported

    c, a, b, *_ = _stack_arrays(np.float32)
    set_config(mm_driver="pallas")
    assert _pallas_supported(get_config(), c, a, b)


def test_fake_tpu_auto_selects_pallas_f32(fake_tpu):
    """On (pretend) TPU, an untuned f32 stack auto-dispatches to the
    Pallas family (crosspack default for untuned f32 shapes)."""
    from dbcsr_tpu.acc.smm import _pallas_supported, prepare_stack

    c, a, b, ai, bi, ci = _stack_arrays(np.float32)
    set_config(mm_driver="auto", use_pallas=True)
    assert _pallas_supported(get_config(), c, a, b)
    plan = prepare_stack(c, a, b, ai, bi, ci)
    assert plan.driver.startswith("pallas"), plan.driver


def test_fake_tpu_f64_gets_r_tiled_group_driver(fake_tpu):
    """Emulated-dtype (f64) stacks on TPU take the R-tiled xla_group
    layout — the MXU-starvation counter (PERF_NOTES)."""
    from dbcsr_tpu.acc.smm import emulated_dtype_on_tpu, prepare_stack

    assert emulated_dtype_on_tpu(np.float64)
    assert not emulated_dtype_on_tpu(np.float32)
    c, a, b, ai, bi, ci = _stack_arrays(np.float64)
    set_config(mm_driver="auto")
    plan = prepare_stack(c, a, b, ai, bi, ci)
    assert plan.driver == "xla_group", plan.driver
    assert plan.r_grp == 8


def test_f64_off_tpu_is_not_r_tiled():
    from dbcsr_tpu.acc.smm import emulated_dtype_on_tpu, prepare_stack

    assert not emulated_dtype_on_tpu(np.float64)
    c, a, b, ai, bi, ci = _stack_arrays(np.float64)
    set_config(mm_driver="auto")
    plan = prepare_stack(c, a, b, ai, bi, ci)
    assert plan.driver != "xla_group", plan.driver


def test_mesh_stack_r0_follows_seam(fake_tpu):
    from dbcsr_tpu.parallel.sparse_dist import _stack_r0

    assert _stack_r0(np.float64) == 8
    assert _stack_r0(np.float32) == 0


def test_mesh_stack_r0_off_tpu():
    from dbcsr_tpu.parallel.sparse_dist import _stack_r0

    assert _stack_r0(np.float64) == 0


def test_host_driver_unavailable_on_fake_tpu(fake_tpu):
    """On a TPU a host round-trip per stack would be catastrophic;
    pretend-TPU must refuse the host driver too."""
    from dbcsr_tpu.acc.smm import _host_smm_available

    assert not _host_smm_available(np.float64)


def test_host_driver_requires_real_cpu_backend(monkeypatch):
    """platform_override='cpu' on a REAL TPU must not make the host
    driver eligible — it changes where compute RUNS (a
    device->host->device round trip per stack), and execution-level
    choices always follow the real platform."""
    import jax

    from dbcsr_tpu.acc.smm import _host_smm_available

    class _FakeTpuDev:
        platform = "tpu"

    assert _host_smm_available(np.float64)  # real cpu backend: eligible
    set_config(platform_override="cpu")
    try:
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpuDev()])
        assert not _host_smm_available(np.float64)
    finally:
        monkeypatch.undo()
        set_config(platform_override="")


def _fill_pair(occ=0.5, nblk=20, bs=8):
    rng = np.random.default_rng(7)
    rbs = [bs] * nblk
    a = dt.make_random_matrix("A", rbs, rbs, dtype=np.float64,
                              occupation=occ, rng=rng)
    b = dt.make_random_matrix("B", rbs, rbs, dtype=np.float64,
                              occupation=occ, rng=rng)
    c = dt.create("C", rbs, rbs, dtype=np.float64)
    return a, b, c


def _plan(a, b, c, filter_eps=None, retain_sparsity=False):
    from dbcsr_tpu.mm import format_planner as fp

    return fp.choose(a, b, c, filter_eps=filter_eps,
                     retain_sparsity=retain_sparsity, no_limits=True,
                     dense=True)


def test_dense_cost_model_routes_f64_on_fake_tpu(fake_tpu):
    """The emulated-dtype cost model (dense beats MXU-starved sparse
    stacks by ~320x for f64) is TPU-only; the seam makes the routing
    assertable on the CPU suite."""
    a, b, c = _fill_pair()
    set_config(mm_driver="auto")
    plan = _plan(a, b, c)
    assert (plan.fmt, plan.reason, plan.why) == (
        "dense", "heuristic", "cost-model:emulated-dtype")


def test_dense_cost_model_refusals(fake_tpu):
    from dbcsr_tpu.mm import format_planner as fp

    a, b, c = _fill_pair()
    set_config(mm_driver="auto")
    # filter_eps produces a filtered C: dense mode must refuse
    plan = _plan(a, b, c, filter_eps=1e-9)
    assert (plan.fmt, plan.reason) == ("stack", "structural")
    # retain_sparsity keeps C's pattern: refuse
    plan = _plan(a, b, c, retain_sparsity=True)
    assert (plan.fmt, plan.reason) == ("stack", "structural")
    # a forced stack driver wins over the cost model
    set_config(mm_driver="xla")
    assert fp._dense_rule(a, b, c, get_config(), True) is None
    set_config(mm_driver="auto")
    # structurally sparse C (block-diagonal operands): expected fill
    # far below 0.5 — must not silently densify
    rbs = [8] * 20
    ad = dt.create("Ad", rbs, rbs, dtype=np.float64)
    bd = dt.create("Bd", rbs, rbs, dtype=np.float64)
    rng = np.random.default_rng(3)
    for i in range(20):
        ad.put_block(i, i, rng.standard_normal((8, 8)))
        bd.put_block(i, i, rng.standard_normal((8, 8)))
    ad.finalize()
    bd.finalize()
    cd = dt.create("Cd", rbs, rbs, dtype=np.float64)
    assert fp._dense_rule(ad, bd, cd, get_config(), True) is None
    assert _plan(ad, bd, cd).fmt != "dense"


def test_dense_cost_model_off_tpu_is_dead():
    """f64 is native on CPU; the emulated-dtype branch must not fire."""
    a, b, c = _fill_pair()
    set_config(mm_driver="auto")
    plan = _plan(a, b, c)
    assert (plan.fmt, plan.reason) == ("stack", "default")
