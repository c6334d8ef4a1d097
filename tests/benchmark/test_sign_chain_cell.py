"""The cell `h2o_ls_chain.sign_f64` on the CPU: the operand H, the plain
reference chain against dense NumPy, its candidate test against a triple
loop in float32, its stacks against what the program returns, and that
a chain computed otherwise (float32, no filter, no candidate test) is
not `correct`.  Counts and results only; no number here is a rate."""

import json
import os
import types

import numpy as np
import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, reference
from benchmark.fixtures.tiny import REPO, tiny_checkout

CELL = "h2o_ls_chain.sign_f64"
sc = harness._load_code(os.path.join(REPO, "benchmark", "generators",
                                     "sign_chain.py"))
with open(os.path.join(REPO, "benchmark", "configs",
                       "h2o_ls_chain.json")) as _fh:
    FULL = json.load(_fh)
with open(os.path.join(REPO, "benchmark", "traffic", "sign_f64.json")) as _fh:
    TRAFFIC = json.load(_fh)
RECIPE = {k: FULL["assumed"][k]["value"]
          for k in ("occupied_per_block", "coupling", "decay_length",
                    "virtual_width")}
EPS = float(FULL["filter_eps"])
BENCH = types.SimpleNamespace(arithmetic=arithmetic, reference=reference)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_checkout(str(tmp_path_factory.mktemp("tiny_chain")))


@pytest.fixture(scope="module")
def tiny_config(tiny):
    return harness.Cell(tiny, CELL).config


@pytest.fixture()
def restore_config():
    prev = dt.get_config().incremental
    yield
    dt.set_config(incremental=prev)


def _generator(config, seed):
    gen = sc.Generator(BENCH, config, TRAFFIC, seed, [])
    gen.make_operands()
    return gen


def _stage(blocks):
    """A reference matrix as the program's, through the public API."""
    sizes = blocks.sizes.astype(np.int32)
    m = dt.create("X", sizes, sizes, "float64")
    for rows, cols, data in blocks.by_shape():
        m.put_blocks(rows, cols, data)
    return m.finalize()


# ------------------------------------------------------------ the operand
@pytest.mark.parametrize("block,occupancy",
                         [(23, FULL["occupancy"]["a"]), (5, 0.4)],
                         ids=["full_file", "rehearsal"])
def test_h_is_symmetric_gapped_and_at_the_asked_occupancy(block, occupancy):
    """60 molecules under the full file's parameters and the
    rehearsal's: the filter drops a block and the candidate test
    prunes a candidate in both, or the cell measures another
    deployment."""
    sizes = arithmetic.expand_block_sizes(60 * block - 2, [[1, block]])
    assert len(sizes) == 60 and sizes[-1] == block - 2
    h = sc.draw_hamiltonian(sizes, occupancy, FULL["pattern_seed"], 7,
                            **RECIPE)
    assert abs(len(h.rows) - occupancy * 60 * 60) <= 1
    dense = h.dense()
    assert np.array_equal(dense, dense.T)
    g = sc.gershgorin(h)
    assert g == pytest.approx(np.abs(dense).sum(axis=1).max(), rel=1e-14)
    assert g <= RECIPE["virtual_width"] + 0.25
    eig = np.linalg.eigvalsh(dense / g)
    assert np.abs(eig).min() > 0.2
    occupied = int(sc.occupied_of(sizes, RECIPE["occupied_per_block"]).sum())
    assert (eig < 0).sum() == occupied
    # other values, the same positions
    again = sc.draw_hamiltonian(sizes, occupancy, FULL["pattern_seed"], 8,
                                **RECIPE)
    assert np.array_equal(again.keys, h.keys)
    assert not np.array_equal(again.data, h.data)
    chain = sc.reference_chain(
        h, filter_eps=EPS, tol=FULL["assumed"]["tol"]["value"],
        max_steps=FULL["assumed"]["max_steps"]["value"])
    assert chain.history[-1] < FULL["assumed"]["tol"]["value"]
    assert sum(p["pruned"] for p in chain.products) >= 1
    assert sum(p["c_dropped"] for p in chain.products) >= 1


def test_occupied_orbitals_follow_the_block_size():
    assert sc.occupied_of([23, 18, 5, 3, 1], 4).tolist() == [4, 3, 1, 1, 1]


# ---------------------------------------------------------- the reference
def test_reference_chain_is_the_sign_function_with_the_filter_off():
    """12 molecules, no filter and no candidate test: Newton-Schulz to
    convergence against sign(H) by eigendecomposition."""
    sizes = arithmetic.expand_block_sizes(12 * 23 - 5, [[1, 23]])
    h = sc.draw_hamiltonian(sizes, 0.5, 3, 5, **RECIPE)
    chain = sc.reference_chain(h, filter_eps=None, tol=1e-13, max_steps=30)
    assert chain.steps < 30 and chain.history[-1] < 1e-13
    w, v = np.linalg.eigh(h.dense())
    want = (v * np.sign(w)) @ v.T
    assert np.abs(chain.x.dense() - want).max() < 1e-12
    occupied = int(sc.occupied_of(sizes, 4).sum())
    assert np.trace(chain.x.dense()) == pytest.approx(
        sizes.sum() - 2 * occupied, abs=1e-10)
    assert all(p["pruned"] == 0 and p["c_dropped"] == 0
               for p in chain.products)


def test_candidate_test_is_the_float32_rule_by_a_triple_loop():
    sizes = arithmetic.expand_block_sizes(98, [[1, 5]])  # 19 x 5 + 3
    h = sc.draw_hamiltonian(sizes, 1.0, 3, 9, **RECIPE)
    x = sc.Blocks(h.sizes, h.rows, h.cols, h.data / sc.gershgorin(h))
    x2, _ = sc.filtered_product(x, x, 1.0, EPS)
    kept = pruned = 0
    for a, b in ((x, x), (x, x2)):
        a_ent, b_ent, keep, _, _ = sc.candidates(a, b, EPS)
        got = {(int(a.rows[e]), int(a.cols[e]), int(b.cols[f])): bool(k)
               for e, f, k in zip(a_ent, b_ent, keep)}
        na = {(int(r), int(c)): np.float32(np.linalg.norm(d))
              for r, c, d in zip(a.rows, a.cols, a.data)}
        nb_ = {(int(r), int(c)): np.float32(np.linalg.norm(d))
               for r, c, d in zip(b.rows, b.cols, b.data)}
        in_row = np.bincount(a.rows, minlength=a.nb)
        want = {}
        for (i, k), an in na.items():
            for (k2, j), bn in nb_.items():
                if k2 != k:
                    continue
                eps = np.float32(EPS) / np.float32(max(1, in_row[i]))
                want[(i, k, j)] = bool(
                    (an * an) * (bn * bn) >= eps * eps)
        assert got == want
        kept += sum(want.values())
        pruned += len(want) - sum(want.values())
    assert kept > 0 and pruned > 0


def test_chain_stacks_carry_the_sum_of_the_products_bytes(tiny_config):
    gen = _generator(tiny_config, 4)
    products = gen.chain().products
    stacks = gen.stacks(0)
    assert arithmetic.true_flops(stacks) == sum(
        arithmetic.true_flops(p["stacks"]) for p in products) == gen.flops(0)
    assert arithmetic.fused_stack_bytes(stacks, 8) == sum(
        arithmetic.fused_stack_bytes(p["stacks"], 8) for p in products)
    assert len(stacks) == sum(len(p["stacks"]) for p in products)


# ------------------------------------- the program against the reference
@pytest.mark.parametrize("seed", list(range(19)) + [2 ** 31 + 11])
def test_reference_stacks_are_the_flops_the_program_returns(
        tiny_config, seed, restore_config):
    dt.set_config(**TRAFFIC["program_config"])
    gen = _generator(tiny_config, seed)
    x, flops = gen.start(0)
    assert flops == gen.flops(0) > 0
    check = gen.check(0, x)
    assert check["ok"], check
    assert check["steps"] == check["steps_reference"] == x._last_steps
    assert check["rel_err"] <= 1e-14


@pytest.mark.parametrize("variant", ["float32", "no_filter",
                                     "no_candidate_test"])
def test_a_chain_computed_otherwise_is_not_correct(tiny_config, variant):
    """What `correct` is there to catch, each by orders and not by a
    hair: the products in float32, the filter skipped, the candidate
    test skipped.  The variant's X goes through the same check as the
    program's."""
    gen = _generator(tiny_config, 6)
    kw = {"float32": {"compute": np.float32}, "no_filter": {"drop": False},
          "no_candidate_test": {"prune": False}}[variant]
    other = sc.reference_chain(gen.h, filter_eps=gen.filter_eps, tol=gen.tol,
                               max_steps=gen.max_steps, **kw)
    gen._history = other.history
    check = gen.check(0, _stage(other.x))
    flops = arithmetic.true_flops(sc.chain_stacks(other.products))
    # the harness's `correct`: the check and the flops, to the flop
    assert not (check["ok"] and flops == gen.flops(0)), check
    if variant == "float32":
        assert check["rel_err"] > 1e4 * check["tol"]
    elif variant == "no_filter":
        assert not check["same_pattern"]
        assert check["x_blocks"] > check["x_blocks_reference"]
    else:
        assert flops > gen.flops(0)
        assert check["rel_err"] > 10 * check["tol"]
    # and the chain as stated passes it
    same = sc.reference_chain(gen.h, filter_eps=gen.filter_eps, tol=gen.tol,
                              max_steps=gen.max_steps)
    gen._history = same.history
    assert gen.check(0, _stage(same.x))["ok"]


def test_traced_rehearsal_reports_the_chain_metrics(
        tiny, monkeypatch, capsys, restore_config, synthetic_opmeta):
    """A traced run on the CPU with the hand-written trace: the three
    counter metrics read what the chains of the window did, the span
    and module metrics find nothing in that trace and are left out."""
    from benchmark import xplane

    with open(os.path.join(REPO, "benchmark", "fixtures",
                           "synthetic_trace.json")) as fh:
        synthetic = json.load(fh)
    monkeypatch.setattr(xplane, "load", lambda path, keep=None: synthetic)
    info = harness._device_info
    monkeypatch.setattr(harness, "_device_info",
                        lambda devs: dict(info(devs), kind="TPU v5 lite"))
    assert harness.run_cell(tiny, CELL, 3, 0.3, True, platform="cpu") == 0
    out = capsys.readouterr().out.splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    chain = [json.loads(ln.split(" ", 2)[2]) for ln in out
             if ln.startswith("BENCH chain ")]
    got = line["metrics"]
    assert got["candidates_pruned"]["value"] == \
        sum(p["pruned"] for p in chain) > 0
    assert got["filter_dropped_blocks"]["value"] == \
        sum(p["c_dropped"] for p in chain) > 0
    # the same H every chain: from the second chain on every plan hits
    assert got["plan_cache_misses"]["value"] == 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["launches_per_multiply"]["value"] > 0
    assert got["stack_hbm_share"]["value"] > 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    listed = {m["name"] for m in spec["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    silent = {"chain_add_s", "chain_host_s", "filter_host_s"}
    assert set(got) == listed - silent
