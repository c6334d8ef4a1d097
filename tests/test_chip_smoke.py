"""chip_smoke.py on the CPU: the leg bodies at a few blocks, the
no-chip exit, the no-quiet-failover contract, and the compile-cache
placement rule the smoke reports."""

import os

import jax
import pytest

import chip_smoke
from dbcsr_tpu.core import lib
from dbcsr_tpu.resilience import breaker, faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 8 blocks of 5 and a ragged 3, like the north star's 434 x 23 + 18
_TINY = dict(n=43, block=5, occupancy=0.4, seed=3)


@pytest.fixture
def dense_mixed(monkeypatch):
    """At a few blocks the mixed leg's occupancy of 0.05 stores nothing."""
    monkeypatch.setitem(chip_smoke.MIXED, "occupancy", 0.6)


def test_single_device_legs_pass_tiny(capsys, dense_mixed):
    out = chip_smoke.run_legs(**_TINY, mesh=False)
    assert set(out) == {"f64", "f64_filtered", "f32", "f64_filtered_mixed",
                        "sign_chain", "tensor_3c"}
    for leg, res in out.items():
        assert res["leg"] == leg and len(res["steady_s"]) == 2
        assert res["driver_launches"], leg  # names the driver that ran
    assert out["f64_filtered"]["checksum"] == pytest.approx(
        out["f64"]["checksum"], rel=1e-9)
    lines = capsys.readouterr().out.splitlines()
    assert sum(line.startswith("CHECK ") for line in lines) == 6
    # three steps of the sign chain, held to the benchmark's NumPy chain
    chain = out["sign_chain"]
    assert chain["algorithm"] == "stack" and chain["flops"] > 0


def test_mixed_leg_is_held_to_numpy_on_a_row_of_every_block_size(
        capsys, dense_mixed):
    """`f64_filtered_mixed` at 120 rows: blocks {5, 13, 23} cycled and a
    ragged 20, filtered f64 through the stack engine, every row-block
    size among the rows NumPy checks, no failover."""
    import json

    out = chip_smoke.run_legs(**dict(_TINY, n=120),
                              legs=("f64_filtered_mixed",))
    res = out["f64_filtered_mixed"]
    assert res["algorithm"] == "stack" and res["driver_launches"]
    assert res["flops"] > 0 and len(res["steady_s"]) == 2
    lines = capsys.readouterr().out.splitlines()
    check, = (json.loads(ln.split(" ", 1)[1]) for ln in lines
              if ln.startswith("CHECK "))
    assert check["check"] == "numpy_rows"
    assert check["row_block_sizes"] == [5, 13, 20, 23]
    assert check["rel_err"] <= check["tol"] < 1e-13
    sizes = chip_smoke._block_sizes(120, chip_smoke.MIXED["blocks"])
    assert check["rows"] >= 5 + 13 + 20 + 23 and sizes.sum() == 120
    # the uniform legs sample as before: first, last, random picks
    rows = chip_smoke._sample_rows(chip_smoke._block_sizes(43, 5), 3)
    assert rows[0] == 0 and rows[-1] == 8
    assert len(rows) == chip_smoke.N_SAMPLE_ROWS


@pytest.mark.parametrize("leg", ["mesh4", "mesh4_filtered",
                                 "sign_chain_mesh4"])
def test_mesh_leg_is_never_silently_absent(capsys, monkeypatch, leg):
    monkeypatch.setattr(jax, "devices", lambda *a: [object()] * 2)
    fn = getattr(chip_smoke, f"leg_{leg}")
    assert fn(**_TINY, reference={}) is None
    assert f"{leg} skipped: 2 device(s)" in capsys.readouterr().out


def test_filtered_mesh_leg_runs_the_sparse_engine_in_both_modes(capsys):
    """`mesh4_filtered` on four of conftest's virtual devices, chosen
    with `legs=` beside the f64 leg it is checked against."""
    out = chip_smoke.run_legs(
        **_TINY, legs=("f64", "mesh4_filtered"))
    assert set(out) == {"f64", "mesh4_filtered"}
    by_mode = out["mesh4_filtered"]
    assert set(by_mode) == {"serial", "double_buffer"}
    for mode, res in by_mode.items():
        assert res["leg"] == f"mesh4_filtered_{mode}"
        assert res["algorithm"] == "stack" and res["grid"]["pr"] == 2
        assert res["checksum"] == pytest.approx(
            out["f64"]["checksum"], rel=1e-9)
    assert by_mode["serial"]["checksum"] == \
        by_mode["double_buffer"]["checksum"]
    lines = capsys.readouterr().out.splitlines()
    assert sum(line.startswith("CHECK ") for line in lines) == 3


def test_sign_chain_leg_on_the_grid_matches_the_one_chip_leg(capsys):
    """`sign_chain_mesh4` on four of conftest's virtual devices, beside
    the `sign_chain` leg it is checked against: the same flops and
    blocks, the checksum to 1e-9."""
    out = chip_smoke.run_legs(
        **_TINY, legs=("sign_chain", "sign_chain_mesh4"))
    assert set(out) == {"sign_chain", "sign_chain_mesh4"}
    one, grid = out["sign_chain"], out["sign_chain_mesh4"]
    assert grid["algorithm"] == "stack" and grid["grid"]["pr"] == 2
    assert (grid["flops"], grid["nblks"]) == (one["flops"], one["nblks"])
    assert grid["checksum"] == pytest.approx(one["checksum"], rel=1e-9)
    lines = capsys.readouterr().out.splitlines()
    assert sum(line.startswith("CHECK ") for line in lines) == 2
    assert any("checksum_vs_sign_chain" in line for line in lines)


def test_sign_chain_leg_on_the_grid_needs_its_reference():
    with pytest.raises(chip_smoke.SmokeFailure, match="gave no reference"):
        chip_smoke.run_legs(**_TINY, legs=("sign_chain_mesh4",))


def test_main_rejects_an_unknown_leg(capsys):
    with pytest.raises(SystemExit):
        chip_smoke.main(["--legs", "f64,mesh5"])
    assert "unknown leg" in capsys.readouterr().err


def test_main_without_a_chip_exits_nonzero_naming_the_platform(capsys):
    assert chip_smoke.main([]) == 2
    cap = capsys.readouterr()
    assert "cpu" in cap.err and cap.out == ""


def test_leg_fails_when_a_failover_fires():
    # uniform blocks: one span per C bin, so the per-span
    # execute_stack site (not the fused one) carries the product
    try:
        with faults.inject_faults("execute_stack:raise,times=1"):
            with pytest.raises(chip_smoke.SmokeFailure, match="failover"):
                chip_smoke.leg_f64(n=40, block=5, occupancy=0.4, seed=3)
    finally:
        breaker.reset_board()


def test_compile_cache_placement_rule(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the program sets no other directory
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "/as/jax/read/it")
        assert lib.place_compile_cache() == "/as/jax/read/it"
        # not placed: the fixed, git-ignored directory in the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert lib.place_compile_cache() == os.path.join(_REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            _REPO, ".jax_cache")
        with open(os.path.join(_REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_host_allocator_serves_index_sized_arrays_from_the_heap():
    """`import dbcsr_tpu` fixes glibc's mmap threshold at 32 MiB, so the
    index phase's arrays (several MB each) are not mmapped and
    zero-filled anew every product; larger ones still are."""
    import ctypes

    import numpy as np

    libc = ctypes.CDLL(None)
    if not hasattr(libc, "mallinfo2"):
        pytest.skip("no glibc mallinfo2 here")
    assert lib.steady_host_allocator() is True  # again: same answer

    class MallInfo(ctypes.Structure):
        _fields_ = [(name, ctypes.c_size_t) for name in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
            "fsmblks", "uordblks", "fordblks", "keepcost")]

    libc.mallinfo2.argtypes, libc.mallinfo2.restype = (), MallInfo

    def mmapped_chunks_after(nbytes):
        before = libc.mallinfo2().hblks
        held = np.empty(nbytes, np.uint8)
        return libc.mallinfo2().hblks - before, held

    assert mmapped_chunks_after(8 << 20)[0] == 0
    assert mmapped_chunks_after(48 << 20)[0] == 1
