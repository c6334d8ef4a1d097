"""Params rows: exact-shape evidence beats donor prediction, the
tuner stamps where it measured ("env": "onchip"|"cpu"), and rows the
online tuner promotes rank like any other.  Each device kind has its
own parameter file (the reference's `parameters_utils.h` layout), so
provenance is a record, not a vote.
"""

import json

import numpy as np
import pytest

import dbcsr_tpu  # noqa: F401 — jax config via conftest
from dbcsr_tpu.acc import params as params_mod


@pytest.fixture
def table(tmp_path, monkeypatch):
    path = tmp_path / "parameters_test.json"
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    monkeypatch.setattr(params_mod, "params_path",
                        lambda kind=None: str(path))
    params_mod._cache.clear()
    params_mod._predict_cache.clear()
    yield path
    params_mod._cache.clear()
    params_mod._predict_cache.clear()


def _write(path, rows):
    path.write_text(json.dumps(rows))
    params_mod._cache.clear()
    params_mod._predict_cache.clear()


def test_predict_exact_shape_beats_permuted_donor(table):
    """Permuted shapes share the m*n*k product, so the donor distance
    ties at 0 — the exact (m, n, k) row must win, not whichever row
    table iteration order visits first.  A (5,13,23)/(23,13,5) pair
    sorted by (m,n,k), with DIFFERENT tuned r0 (8 vs 16)."""
    donor = {"m": 5, "n": 13, "k": 23, "dtype": "float64",
             "stack_size": 30000, "driver": "xla_group", "grouping": None,
             "r0": 8, "env": "onchip", "gflops": 1.25}
    exact = {"m": 23, "n": 13, "k": 5, "dtype": "float64",
             "stack_size": 30000, "driver": "xla_group", "grouping": None,
             "r0": 16, "env": "onchip", "gflops": 1.38}
    _write(table, [donor, exact])  # donor first = the losing iteration order
    got = params_mod.predict(23, 13, 5, np.float64, stack_size=30000)
    assert (got["m"], got["n"], got["k"]) == (23, 13, 5)
    assert got["r0"] == 16
    # exact evidence, not a donor prediction: the tag gates
    # exactness-only features (bf16 crosspack / pack acceptance)
    assert "predicted_from" not in got
    # and the permuted shape still predicts from its own exact row
    got2 = params_mod.predict(5, 13, 23, np.float64, stack_size=30000)
    assert (got2["m"], got2["n"], got2["k"]) == (5, 13, 23)
    assert got2["r0"] == 8 and "predicted_from" not in got2


def test_predict_exact_shape_beats_donor_at_nearer_stack_size(table):
    """The (5,13,23) donor is tuned at the exact queried stack size (so
    stack-size proximity favors the donor): the exact (23,13,5) row
    must still win, and come back as exact evidence (no
    "predicted_from"), with ITS params, not the donor's."""
    donor = {"m": 5, "n": 13, "k": 23, "dtype": "float64",
             "stack_size": 30000, "driver": "xla_group", "grouping": None,
             "r0": 8, "env": "onchip", "gflops": 6.1}
    exact = {"m": 23, "n": 13, "k": 5, "dtype": "float64",
             "stack_size": 100000, "driver": "xla_group", "grouping": None,
             "r0": 16, "env": "onchip", "gflops": 6.7}
    _write(table, [donor, exact])
    got = params_mod.predict(23, 13, 5, np.float64, stack_size=30000)
    assert (got["m"], got["n"], got["k"]) == (23, 13, 5)
    assert got["r0"] == 16 and "predicted_from" not in got
    # and with no stack size given (larger-S preference would also
    # favor... the exact row here; flip: donor gets the bigger S)
    donor2 = dict(donor, stack_size=200000)
    _write(table, [donor2, exact])
    got = params_mod.predict(23, 13, 5, np.float64)
    assert (got["m"], got["n"], got["k"]) == (23, 13, 5)
    assert got["r0"] == 16 and "predicted_from" not in got


def test_tuner_stamps_real_platform_env():
    from dbcsr_tpu.acc.tune import _measure_env
    from dbcsr_tpu.core.config import set_config

    # provenance records the REAL platform even under the dispatch seam
    set_config(platform_override="tpu")
    try:
        assert _measure_env() == "cpu"
    finally:
        set_config(platform_override="")


# ------------------------------------------------- promoted rows (tune)

def test_promoted_row_outranks_donor_prediction(table):
    """A tuner-promoted exact row is real evidence: it must win over a
    nearest-donor prediction from a neighboring shape of equal
    provenance quality."""
    from dbcsr_tpu.tune import store

    donor = {"m": 32, "n": 32, "k": 32, "dtype": "float64",
             "stack_size": 30000, "driver": "xla_group", "r0": 8,
             "grouping": None, "gflops": 2.0, "env": "cpu"}
    _write(table, [donor])
    got = params_mod.predict(23, 23, 23, np.float64, stack_size=30000)
    assert got["predicted_from"] == (32, 32, 32)  # donor before tuning
    store.promote({"m": 23, "n": 23, "k": 23, "dtype": "float64",
                   "stack_size": 30000, "driver": "host",
                   "grouping": None, "gflops": 4.0, "env": "cpu"})
    got = params_mod.predict(23, 23, 23, np.float64, stack_size=30000)
    assert got["driver"] == "host" and "predicted_from" not in got


def test_promoted_row_never_outranks_fresher_real_evidence(table):
    """Fresher real evidence at the same key (a later offline tune, a
    newer on-chip sweep) overwrites a promoted row — the promotion
    must not pin the cell against better measurement."""
    from dbcsr_tpu.tune import store

    _write(table, [])
    store.promote({"m": 23, "n": 23, "k": 23, "dtype": "float64",
                   "stack_size": 30000, "driver": "xla_flat",
                   "grouping": None, "gflops": 1.5, "env": "cpu"})
    assert params_mod.lookup(
        23, 23, 23, np.float64, stack_size=30000)["driver"] == "xla_flat"
    # fresher real evidence: the offline tuner re-measures the key
    params_mod.save_entry({"m": 23, "n": 23, "k": 23, "dtype": "float64",
                           "stack_size": 30000, "driver": "host",
                           "grouping": None, "gflops": 6.0, "env": "cpu"})
    got = params_mod.lookup(23, 23, 23, np.float64, stack_size=30000)
    assert got["driver"] == "host" and "tuned_by" not in got
    got = params_mod.predict(23, 23, 23, np.float64, stack_size=30000)
    assert got["driver"] == "host"


def test_committed_table_rows_all_tagged():
    import glob
    import os

    pdir = os.path.join(os.path.dirname(params_mod.__file__), "params")
    for path in glob.glob(os.path.join(pdir, "*.json")):
        for e in json.load(open(path)):
            assert e.get("env") in ("onchip", "cpu"), (
                f"untagged row {e} in {os.path.basename(path)}"
            )
