"""acc-layer bench drivers + autotuner/params table tests
(ref `acc_bench_smm.c` validation pattern and `libsmm_acc` tune/merge)."""

import numpy as np

from dbcsr_tpu.acc import params as params_mod
from dbcsr_tpu.acc.bench import bench_smm, bench_trans
import pytest


def test_bench_smm_validates(capsys):
    res = bench_smm(nrep=1, stack_size=300, m=5, n=4, k=6, dtype_enum=3, out=lambda *a: None)
    assert res["errors"] == 0
    assert res["gflops"] > 0


def test_bench_trans_validates():
    res = bench_trans(nrep=1, stack_size=300, m=5, n=7, out=lambda *a: None)
    assert res["errors"] == 0


def test_params_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod._cache.clear()
    assert params_mod.lookup(3, 3, 3, np.float32) is None
    entry = {"m": 3, "n": 3, "k": 3, "dtype": "float32",
             "driver": "pallas", "grouping": 4, "gflops": 1.0}
    params_mod.save_entry(entry)
    params_mod._cache.clear()
    got = params_mod.lookup(3, 3, 3, np.float32)
    assert got is not None and got["grouping"] == 4
    params_mod._cache.clear()


def test_predict_falls_back_to_nearest_tuned_entry(tmp_path, monkeypatch):
    """Untuned (m,n,k) shapes borrow the nearest tuned entry (the
    predict/ ML-pipeline analog, src/acc/libsmm_acc/predict/)."""
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod._cache.clear()
    params_mod.save_entry({"m": 5, "n": 5, "k": 5, "dtype": "float64",
                           "driver": "xla", "grouping": None, "gflops": 10.0})
    params_mod.save_entry({"m": 32, "n": 32, "k": 32, "dtype": "float64",
                           "driver": "xla_flat", "grouping": None, "gflops": 99.0})
    try:
        # exact hit has no prediction tag
        assert "predicted_from" not in params_mod.predict(5, 5, 5, "float64")
        # 30^3 is nearer 32^3 than 5^3 in log-flops
        p = params_mod.predict(30, 30, 30, "float64")
        assert p["driver"] == "xla_flat"
        assert p["predicted_from"] == (32, 32, 32)
        # no same-dtype donors -> no prediction
        assert params_mod.predict(8, 8, 8, "float32") is None
    finally:
        params_mod._cache.clear()


def test_params_stack_size_rows_coexist(tmp_path, monkeypatch):
    """Rows for the same shape at different stack sizes coexist (keyed
    by (m,n,k,dtype,S)), and lookup/predict pick the row nearest the
    live stack size — VERDICT r3 item 3's S>=100k requirement."""
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod._cache.clear()
    params_mod._predict_cache.clear()
    base = {"m": 23, "n": 23, "k": 23, "dtype": "float64",
            "grouping": None, "gflops": 1.0}
    params_mod.save_entry({**base, "stack_size": 30000, "driver": "xla"})
    params_mod.save_entry({**base, "stack_size": 800000,
                           "driver": "xla_group"})
    try:
        # both rows survive in the file
        import json

        with open(params_mod.params_path()) as fh:
            assert len(json.load(fh)) == 2
        # S-aware: near 30k -> the 30k row; near 800k -> the 800k row
        assert params_mod.lookup(23, 23, 23, "float64", 20000)["driver"] == "xla"
        assert (params_mod.lookup(23, 23, 23, "float64", 900000)["driver"]
                == "xla_group")
        # no S -> production scale (largest S)
        assert params_mod.lookup(23, 23, 23, "float64")["driver"] == "xla_group"
        # predict() for an untuned shape prefers the donor tuned nearest
        # the live stack size
        p_small = params_mod.predict(21, 21, 21, "float64", stack_size=30000)
        p_big = params_mod.predict(21, 21, 21, "float64", stack_size=700000)
        assert p_small["driver"] == "xla" and p_big["driver"] == "xla_group"
        assert p_big["predicted_from"] == (23, 23, 23)
    finally:
        params_mod._cache.clear()
        params_mod._predict_cache.clear()


@pytest.mark.slow
def test_tune_smm_writes_entry(tmp_path, monkeypatch):
    from dbcsr_tpu.acc.tune import tune_smm

    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod._cache.clear()
    entry = tune_smm(4, 4, 4, dtype_enum=1, stack_size=200, nrep=1,
                     out=lambda *a: None)
    assert entry["driver"] in ("pallas", "xla", "xla_flat", "xla_group", "host")
    params_mod._cache.clear()
    got = params_mod.lookup(4, 4, 4, np.float32)
    assert got is not None and got["gflops"] > 0
    params_mod._cache.clear()


def test_winning_row_takes_the_driver_from_native_candidates():
    """The v5e's 23^3 f64 sweep (PR 21): the demoted ``xla`` leg was
    the fastest candidate, and as the whole row it sent NATIVE dispatch
    to plain ``xla`` (1.6) where ``xla_group`` does 6.3.  The driver
    must come from the native candidates; a faster demoted candidate
    contributes the ``precision`` column only."""
    from dbcsr_tpu.acc.tune import winning_row

    cands = [
        {"driver": "xla", "grouping": None, "gflops": 1.6},
        {"driver": "xla", "grouping": None, "precision": "f32c",
         "gflops": 0.7},
        {"driver": "xla", "grouping": None, "precision": "f32",
         "gflops": 40.38},
        {"driver": "xla_group", "grouping": None, "r0": 8, "gflops": 6.3},
    ]
    row = winning_row(cands)
    assert (row["driver"], row["r0"], row["gflops"]) == ("xla_group", 8, 6.3)
    assert (row["precision"], row["precision_gflops"]) == ("f32", 40.38)
    # a demoted candidate slower than the native winner stamps nothing
    assert "precision" not in winning_row(cands[:2] + cands[3:])
    assert winning_row(cands[1:3]) is None  # no native evidence, no row
