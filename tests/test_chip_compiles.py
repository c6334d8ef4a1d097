"""Compile-only guards: the Pallas stack kernels at the real shapes of
the `mixed10k` deployment, the emulated-f64 XLA stack body at the
north star's, the largest fused f64 program of `mixed10k_filtered`,
and the sparse mesh engine's programs at
`northstar_2x2_filtered`'s panels, lowered and compiled for a DESCRIBED
TPU v5e (no chip attached, nothing runs).  Interpret mode cannot see what this sees: the
chip's 1 MiB of scalar memory, which a crosspack launch's prefetched
index operands overflowed at (5,5,23) until PR 26; the whole-bin passes
the compiler put into every chunk of a filtered f64 product until
PR 27.

All chip compiles of the suite live in this one file; the topology is
described inside a fixture (one process at a time may load the TPU's
library), which skips where it cannot be.
"""

import json
import os
import re

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_2x2():
    """The described four-chip host; every chip compile of the file
    hangs on it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def mixed10k():
    """Block sizes and the A and B block patterns of the deployment, as
    `benchmark/generators/repeat_product.py` draws them."""
    import sys

    sys.path.insert(0, REPO)
    from benchmark import arithmetic

    with open(os.path.join(REPO, "benchmark/configs/mixed10k.json")) as fh:
        cfg = json.load(fh)
    sizes = {d: arithmetic.expand_block_sizes(cfg[d], cfg["blocks"][d])
             for d in "mnk"}
    rng = np.random.default_rng(cfg["pattern_seed"])
    pa = rng.random((len(sizes["m"]), len(sizes["k"]))) \
        < cfg["occupancy"]["a"]
    pb = rng.random((len(sizes["k"]), len(sizes["n"]))) \
        < cfg["occupancy"]["b"]
    return sizes, pa, pb


def _stack_runs(mixed10k, m, n, k):
    """(entries per C block of the (m,n,k) stack in C's block order,
    A blocks of the (m,k) bin, B blocks of the (k,n) bin)."""
    sizes, pa, pb = mixed10k
    a_mk = pa[sizes["m"] == m][:, sizes["k"] == k]
    b_kn = pb[sizes["k"] == k][:, sizes["n"] == n]
    counts = a_mk.astype(np.int32) @ b_kn.astype(np.int32)
    return counts[counts > 0], int(a_mk.sum()), int(b_kn.sum())


def _shape(one_chip, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_crosspack(one_chip, m, n, k, P, R, nsteps, nc_out, na, nb, nc):
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm

    idx = _shape(one_chip, (nsteps * P * R,), jnp.int32)
    lane = _shape(one_chip, (nsteps * P,), jnp.int32)
    with jax.enable_x64(False):
        return pallas_smm._pallas_crosspack.lower(
            _shape(one_chip, (nc, m, n), jnp.float32),
            _shape(one_chip, (na, k, m), jnp.float32),
            _shape(one_chip, (nb, k, n), jnp.float32),
            idx, idx, lane, lane, _shape(one_chip, (1, 1), jnp.float32),
            P=P, R=R, nc_out=nc_out, interpret=False,
        ).compile()


@pytest.mark.parametrize("mnk", [(5, 5, 23), (13, 5, 5)])
def test_largest_crosspack_launch_of_mixed10k_compiles(one_chip, mixed10k,
                                                       mnk):
    """The launch with the most prefetched bytes that
    `prepare_crosspack_launches` makes of this stack fits the chip."""
    from dbcsr_tpu.acc import pallas_smm
    from dbcsr_tpu.utils.rounding import bucket_size

    m, n, k = mnk
    runs, na, nb = _stack_runs(mixed10k, m, n, k)
    assert runs.sum() > 35000 and runs.max() <= 8  # the shape that overflowed
    c_idx = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    zeros = np.zeros(len(c_idx), np.int32)
    P, R = pallas_smm.choose_pack(m, n, k)
    launches = pallas_smm.prepare_crosspack_launches(
        c_idx, zeros, zeros, 0, 0, P, R)
    assert launches and len(launches) > 1
    big = max(launches, key=lambda lc: lc["ai"].size)
    nsteps = big["cg"].size // P
    assert pallas_smm.crosspack_prefetch_bytes(nsteps, P, R) \
        <= pallas_smm._CROSS_PREFETCH_BUDGET
    compiled = _compile_crosspack(
        one_chip, m, n, k, P, R, nsteps, big["nc_out"],
        bucket_size(na) + 1, bucket_size(nb) + 1, bucket_size(len(runs)))
    assert "tpu_custom_call" in compiled.as_text()


def test_crosspack_prefetch_price_is_the_chips(one_chip):
    """`crosspack_prefetch_bytes` prices what the compiler allocates:
    at (4, 4) the last step count it puts under the chip's SMEM
    compiles, and the first it puts over is refused for smem."""
    from dbcsr_tpu.acc import pallas_smm

    price = pallas_smm.crosspack_prefetch_bytes
    fits, over = 6400, 6480  # 983 040 and 1 048 576 B of operands
    assert price(fits, 4, 4) < pallas_smm._SMEM_BYTES <= price(over, 4, 4)
    _compile_crosspack(one_chip, 5, 5, 23, 4, 4, fits, 8192,
                       20000, 20000, 20000)
    with pytest.raises(Exception, match="smem"):
        _compile_crosspack(one_chip, 5, 5, 23, 4, 4, over, 8192,
                           20000, 20000, 20000)


def test_crosspack_write_back_compiles_as_one_named_program(one_chip):
    """The lane write-back at a full launch's shapes: one module whose
    name the benchmark's `jit__pallas*` glob sees, C updated in place."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm

    nc_out = 4096
    lowered = pallas_smm._pallas_cross_scatter.lower(
        _shape(one_chip, (57344, 5, 5), jnp.float32),
        tuple(_shape(one_chip, (nc_out, 5, 5), jnp.float32)
              for _ in range(4)),
        _shape(one_chip, (4 * nc_out,), jnp.int32))
    text = lowered.compile().as_text()
    assert "HloModule jit__pallas_cross_scatter" in text
    assert "stk_cross_scatter" in text
    assert "input_output_alias" in text


def test_base_kernel_launch_of_mixed10k_compiles(one_chip, mixed10k):
    """The base kernel under the donor row (kmerge, R = 8) at a full
    (13,13,23) launch of the deployment: 4096 steps."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm
    from dbcsr_tpu.utils.rounding import bucket_size

    runs, na, nb = _stack_runs(mixed10k, 13, 13, 23)
    c_idx = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    zeros = np.zeros(len(c_idx), np.int32)
    ai2, bi2, ci2, r_grp = pallas_smm.build_grouped_stack(
        c_idx, zeros, zeros, 0, 0, grouping=8)
    launches = pallas_smm.prepare_launches(ai2, bi2, ci2, r_grp, 0, 0)
    nsteps = max(len(lc[2]) for lc in launches)
    assert nsteps == 4096
    with jax.enable_x64(False):
        compiled = pallas_smm._pallas_process.lower(
            _shape(one_chip, (bucket_size(len(runs)), 13, 13), jnp.float32),
            _shape(one_chip, (bucket_size(na) + 1, 13, 23), jnp.float32),
            _shape(one_chip, (bucket_size(nb) + 1, 23, 13), jnp.float32),
            _shape(one_chip, (nsteps * r_grp,), jnp.int32),
            _shape(one_chip, (nsteps * r_grp,), jnp.int32),
            _shape(one_chip, (nsteps,), jnp.int32),
            _shape(one_chip, (1, 1), jnp.float32),
            r_grp=r_grp, interpret=False, kmerge=True,
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------- mixed10k_filtered.scf_f64 (PR 39)
@pytest.fixture(scope="module")
def mixed_f64_bin(mixed10k):
    """The (23,23) C bin of `mixed10k_filtered.scf_f64`, its largest:
    the spans k = 5, 13, 23 and the ragged 19 as `prepare_stack` plans
    them for f64 on a v5e (`xla_group`, r0 8: the parameter table's
    23^3 f64 row is the donor of all four), from the cell's pattern
    (ids zero: only the shapes are used).  (bin capacity, [(k, tiles,
    A's capacity, B's capacity)])."""
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.utils.rounding import bucket_size

    sizes, pa, pb = mixed10k
    reach = np.zeros(((sizes["m"] == 23).sum(), (sizes["n"] == 23).sum()),
                     bool)
    runs = {}
    for k in np.unique(sizes["k"]):
        a_mk = pa[sizes["m"] == 23][:, sizes["k"] == k]
        b_kn = pb[sizes["k"] == k][:, sizes["n"] == 23]
        counts = a_mk.astype(np.int32) @ b_kn.astype(np.int32)
        reach |= counts > 0
        runs[int(k)] = (counts[counts > 0], int(a_mk.sum()), int(b_kn.sum()))
    nseg = bucket_size(int(reach.sum()))
    spans = []
    for k, (run, na, nb) in sorted(runs.items()):
        c_idx = np.repeat(np.arange(len(run)), run).astype(np.int32)
        zeros = np.zeros(len(c_idx), np.int32)
        tiles = smm.build_group_tiles(
            c_idx, zeros, zeros, 8, na, nb, nseg,
            smm.group_chunk_groups(8, 23, 23, k, 8, 30000))
        spans.append((k, tiles, bucket_size(na), bucket_size(nb)))
    return nseg, spans


def test_mixed_f64_plan_is_runs_of_one_in_four_classes(mixed_f64_bin):
    """Counts, not rates: a C block of one span collects 1.4 products
    here (4.4 at the north star), so class 1 carries the product, and
    the chunk grows as the block shrinks (`GROUP_CHUNK_BYTES`)."""
    nseg, spans = mixed_f64_bin
    assert nseg == 57344 and [k for k, *_ in spans] == [5, 13, 19, 23]
    for k, tiles, _, _ in spans:
        if k == 19:  # the ragged block column: 120 entries, one class
            assert tiles.widths == (1,) and tiles.entries == 120
            continue
        assert tiles.widths == (8, 4, 2, 1)
        assert 35_000 < tiles.entries < 36_100
        assert tiles.groups[3] > tiles.entries / 2 > 10 * tiles.groups[0]
        assert 0.85 < tiles.entries / tiles.slots_launched < 0.89
    slots_a_chunk = {k: sum(ga.shape[1] * ga.shape[2]
                            for ga, _, _ in tiles.tiles)
                     for k, tiles, _, _ in spans}
    assert slots_a_chunk[5] > 2 * slots_a_chunk[13] > 3 * slots_a_chunk[23]
    assert 2048 <= slots_a_chunk[23] < 2300  # the north star's 2 048


def test_largest_fused_f64_program_of_mixed10k_compiles_sliced(
        one_chip, mixed_f64_bin):
    """`jit_fused_superstack_sliced` of that bin compiles for the v5e:
    one `while` a span carries the bin, and inside each the dots are
    native bf16 convolutions of slices cut once per stored block, one a
    width class, at strip depths of 5, 13, 19 and 23 a slot: no f64 dot
    that the compiler would expand per gathered strip."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm

    nseg, spans = mixed_f64_bin
    sig, flat = [], []
    for k, tiles, cap_a, cap_b in spans:
        idx = tiles.flat()
        # a pad row past the stored blocks is there: nothing appended
        sig.append(("xla_group", 1 + len(idx), False, False, 8, False, None,
                    "sliced"))
        flat += [_shape(one_chip, (cap_a, 23, k), jnp.float64),
                 _shape(one_chip, (cap_b, k, 23), jnp.float64),
                 _shape(one_chip, (1,), jnp.int32)]
        flat += [_shape(one_chip, x.shape, jnp.int32) for x in idx]
    with jax.enable_x64(True):
        compiled = smm._fused_fn(("xla", False, tuple(sig))).lower(
            _shape(one_chip, (nseg, 23, 23), jnp.float64),
            _shape(one_chip, (), jnp.float64), *flat).compile()
    mem = compiled.memory_analysis()
    print(f"mixed10k_filtered (23,23) bin: temp_size_in_bytes "
          f"{mem.temp_size_in_bytes}, generated_code_size_in_bytes "
          f"{mem.generated_code_size_in_bytes}")
    assert mem.temp_size_in_bytes < 4 * 2 ** 30  # 3.18 GiB, PR 39
    text = compiled.as_text()
    assert "fused_superstack_sliced" in text
    comps = _computations(text)
    bin_shape = f"[{nseg},23,23]"
    loops = [name for name in set(re.findall(
        r"\bwhile\(.*?body=%?([\w.\-]+)", text))
        if any(bin_shape in ln for ln in comps[name])]
    assert len(loops) == len(spans), sorted(loops)
    classes = []
    for name in loops:
        convs = [ln for lines in _reached(comps, comps[name]) for ln in lines
                 if re.search(r" convolution\(", ln)]
        classes.append(len(convs))
        _assert_split_once_not_per_slot(comps, comps[name], len(convs))
    assert sorted(classes) == sorted(len(t.widths) for _, t, _, _ in spans)
    # and no dot or convolution of the program takes f32 or f64 operands
    assert text.count(" convolution(") == sum(classes)
    assert not re.search(r" dot\(", text)


# `northstar.scf_f64`'s 23^3 span: the C bin, the A and B bins with
# their pad row, and the plan `build_group_tiles` makes of its stack
_NS_BIN, _NS_AB, _NS_R0 = 196608, 18901, 8
_NS_BIN_SHAPE = f"[{_NS_BIN},23,23]"
# `generated_code_size_in_bytes` of the span's program: the loaded
# executable lives in HBM and `peak_hbm_gib` counts it (PR 29).  The
# one-class program was 10.9 MB and the cell's peak 1 988.6 MB; the
# index arrays shrank by 1.5 MB, so 1% of the peak leaves 21 MB more.
# Three classes compiled to 17.4 MB here and on the chip (PR 31),
# 16 783 872 B as PR 34 left it (jax 0.9.0, libtpu 0.0.34, compiled
# here): the sliced form of PR 35 has one dot a class where the
# compiler's had three `while`s and nine convolutions, and may not
# take more.
_NS_CODE_BUDGET = 16_783_872


def _reached(comps, lines):
    """``lines`` and, to any depth, the lines of every computation they
    call (fusions, nested loops, reducers)."""
    out, todo, seen = [], [lines], set()
    while todo:
        cur = todo.pop()
        out.append(cur)
        for ln in cur:
            for name in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", ln):
                if name not in seen and name in comps:
                    seen.add(name)
                    todo.append(comps[name])
    return out


def _assert_split_once_not_per_slot(comps, body, classes: int):
    """What PR 35 is: inside a chunk loop of the sliced form nothing
    cuts a gathered operand into slices (no `remainder`, the compiler's
    cut of an emulated-f64 dot's operands, and no split of an f64 into
    its halves but the chunk's product on its way into C), and the
    MXU's work is ONE bf16 convolution a width class."""
    reached = _reached(comps, body)
    flat = [ln for lines in reached for ln in lines]
    assert not any(re.search(r" remainder\(", ln) for ln in flat)
    splits = [ln for ln in flat if "X64Split" in ln]
    assert not any("stk_gather" in ln for ln in splits), splits[:2]
    assert not any(re.search(r"f32\[\d+,\d+,\d{3,}", ln) for ln in splits), \
        splits[:2]  # nothing strip-sized: at most the (ch, m, n) product
    convs = [ln for ln in flat if re.search(r" convolution\(", ln)]
    assert len(convs) == classes, [ln[:200] for ln in convs]
    type_of = dict(re.match(r"(?:ROOT )?%?([\w.\-]+) = (\w+)\[", ln).groups()
                   for ln in flat if re.match(r"(?:ROOT )?%?[\w.\-]+ = \w+\[",
                                              ln))
    for ln in convs:
        lhs, rhs = re.search(r" convolution\(%?([\w.\-]+), %?([\w.\-]+)\)",
                             ln).groups()
        assert (type_of[lhs], type_of[rhs]) == ("bf16", "bf16"), ln[:300]
        assert ln.split(" = ", 1)[1].startswith("f32["), ln[:300]


def _computations(hlo_text):
    """{computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    return comps


@pytest.fixture(scope="module")
def ns_plan():
    """The plan of the cell's 23^3 stack, from its pattern as the
    generator draws it (ids zero: only the shapes are used)."""
    import sys

    sys.path.insert(0, REPO)
    from benchmark import arithmetic
    from dbcsr_tpu.acc import smm

    with open(os.path.join(REPO, "benchmark/configs/northstar.json")) as fh:
        cfg = json.load(fh)
    sizes = arithmetic.expand_block_sizes(cfg["m"], cfg["blocks"]["m"])
    rng = np.random.default_rng(cfg["pattern_seed"])
    pa = rng.random((len(sizes), len(sizes))) < cfg["occupancy"]["a"]
    pb = rng.random((len(sizes), len(sizes))) < cfg["occupancy"]["b"]
    full = sizes == 23
    counts = pa[full][:, full].astype(np.int32) \
        @ pb[full][:, full].astype(np.int32)
    runs = counts[counts > 0]
    assert runs.sum() == 828537  # the stack PERF.md counts
    c_idx = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    zeros = np.zeros(len(c_idx), np.int32)
    return smm.build_group_tiles(
        c_idx, zeros, zeros, _NS_R0, _NS_AB - 1, _NS_AB - 1, _NS_BIN,
        smm.group_chunk_groups(_NS_R0, 23, 23, 23, 8, 30000))


def test_north_star_plan_launches_the_slots_that_hold_entries(ns_plan):
    """Counts, not rates: until PR 31 the plan launched 1 680 000
    slots for these 828 537 entries (fill 49%)."""
    assert ns_plan.widths == (8, 4, 2)
    assert ns_plan.slots_launched < 1_120_000
    assert ns_plan.entries / ns_plan.slots_launched >= 0.74
    assert [ga.shape[1] for ga, _, _ in ns_plan.tiles] == [176, 144, 64]


@pytest.fixture(scope="module")
def ns_group_program(one_chip, ns_plan):
    """`_process_stack_xla_group` compiled at the north star's shapes
    and plan, in the form a TPU plans for f64 (`group_dot_form`:
    "sliced"; the platform here is the CPU, so the test says it): (the
    compiled program, its computations, the lines of its chunk loop's
    body)."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm

    idx = [_shape(one_chip, x.shape, jnp.int32) for x in ns_plan.flat()]
    with jax.enable_x64(True):
        compiled = smm._process_stack_xla_group.lower(
            _shape(one_chip, (_NS_BIN, 23, 23), jnp.float64),
            _shape(one_chip, (_NS_AB, 23, 23), jnp.float64),
            _shape(one_chip, (_NS_AB, 23, 23), jnp.float64),
            _shape(one_chip, (1,), jnp.int32), *idx,
            _shape(one_chip, (), jnp.float64), dot_form="sliced",
        ).compile()
    text = compiled.as_text()
    comps = _computations(text)
    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))
    # ONE `while` carries the bin, whatever the number of classes: what
    # the compiler puts at such a loop (a convert and copies of the
    # carry) cost 0.3 s a product (PERF.md, PR 29)
    chunk_loops = [name for name in bodies
                   if any(_NS_BIN_SHAPE in ln for ln in comps[name])]
    assert len(chunk_loops) == 1, sorted(chunk_loops)
    return compiled, comps, comps[chunk_loops[0]]


@pytest.fixture(scope="module")
def ns_group_hlo(ns_group_program):
    _, comps, body = ns_group_program
    return comps, body


def test_f64_group_program_fits_the_peak_hbm_bound(ns_group_program):
    compiled, _, _ = ns_group_program
    size = compiled.memory_analysis().generated_code_size_in_bytes
    assert size <= _NS_CODE_BUDGET, size


def test_f64_group_body_touches_the_bin_only_in_its_scatters(ns_group_hlo,
                                                             ns_plan):
    """Inside the chunk loop of `_stack_phases_group`, at the north
    star's shapes, nothing but the scatter fusions, one a width class,
    produces an array of the C bin's shape: no zero-fill, add or copy
    of the whole bin per chunk (PR 26's program had five: 2.8 s of a
    6.79 s product)."""
    _, body = ns_group_hlo
    # `%name = <result type> <opcode>(<operands>`: a layout's `T(8,128)`
    # follows a colon, an opcode a space
    made = [(ln, re.search(r" ([a-z][\w\-]*)\(", ln.split(" = ", 1)[1]))
            for ln in body if " = " in ln]
    makers = [ln for ln, op in made
              if _NS_BIN_SHAPE in ln.split(" = ", 1)[1][:op.start()]
              and op.group(1) not in ("get-tuple-element", "parameter",
                                      "tuple")]
    assert len(makers) == len(ns_plan.widths), [ln[:160] for ln in makers]
    assert all("stk_accum/scatter-add" in ln for ln in makers), \
        [ln[:400] for ln in makers]


def test_f64_group_body_gathers_whole_slice_blocks(ns_group_hlo, ns_plan):
    """Inside the same chunk loop every `gather` of operand values
    reads bf16 slice blocks (`_slice_blocks`) from an array whose
    minor-most dimension is not the block index (until PR 29 it was:
    `f32[18901,23,23]{0,2,1}`, 529 x 30 000 single elements fetched
    along lanes per gather, 1.3 s of a 3.9 s product), two a width
    class; no NaN fill is selected over what was gathered (the ids are
    promised in bounds); what is gathered is the dot's operand as it
    stands, (ch, w*32, 8*24) and (ch, w*32, 8*23) with no copy
    between; and the cut is made once per stored block, not per
    slot."""
    comps, body = ns_group_hlo
    reached = _reached(comps, body)
    gathers = 0
    for lines in reached:
        layout_of = {}
        for ln in lines:
            made = re.match(r"(?:ROOT )?%?([\w.\-]+) = \w+\[[\d,]*\]"
                            r"\{([\d,]+)", ln)
            if made:
                layout_of[made.group(1)] = made.group(2).split(",")
        for ln in lines:
            op = re.search(r" gather\(%?([\w.\-]+),", ln)
            if op and "bf16[" in ln.split(" = ", 1)[1][:9]:
                gathers += 1
                assert layout_of[op.group(1)][0] != "0", ln[:300]
            assert not (op and "f32[" in ln.split(" = ", 1)[1][:8]), ln[:300]
    assert gathers == 2 * len(ns_plan.widths)
    assert not any("constant(nan)" in ln for lines in reached
                   for ln in lines)
    text = "\n".join(body)
    for (ga, _, _), w in zip(ns_plan.tiles, ns_plan.widths):
        slots = ga.shape[1] * w
        assert f"bf16[{slots},32,192]{{2,1,0" in text  # A's blocks, on end
        assert f"bf16[{slots},32,184]{{2,1,0" in text  # B's
    # no relayout of a strip between its gather and its dot
    assert not [ln for ln in body if re.search(r" (copy|transpose)\(", ln)
                and "bf16[" in ln.split(" = ", 1)[1][:9]]
    _assert_split_once_not_per_slot(comps, body, len(ns_plan.widths))


def _op_counts(hlo_text):
    """{opcode: instructions} over every computation of a module."""
    ops = {}
    for lines in _computations(hlo_text).values():
        for ln in lines:
            op = re.match(r"(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(", ln)
            if op:
                ops[op.group(1)] = ops.get(op.group(1), 0) + 1
    return dict(sorted(ops.items()))


def _small_group_program(one_chip):
    """`_process_stack_xla_group` in the sliced form at a fixed small
    shape: three width classes."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm

    def tile(ch, w):
        return (_shape(one_chip, (3, ch, w), jnp.int32),) * 2 \
            + (_shape(one_chip, (3, ch), jnp.int32),)

    with jax.enable_x64(True):
        return smm._process_stack_xla_group.lower(
            _shape(one_chip, (64, 5, 4), jnp.float64),
            _shape(one_chip, (9, 5, 3), jnp.float64),
            _shape(one_chip, (9, 3, 4), jnp.float64),
            _shape(one_chip, (1,), jnp.int32),
            *tile(16, 8), *tile(24, 2), *tile(8, 1),
            _shape(one_chip, (), jnp.float64), dot_form="sliced",
        ).compile()


# optimised HLO of `_process_stack_xla_group` in the sliced form for a
# described v5e as PR 35's tree compiled it (jax 0.9.0, libtpu 0.0.34),
# by opcode: the cut once per stored block outside the loop (its
# shifts, compares and selects), in the loop one `while`, one
# `convolution`, two `gather`s and one `scatter` a width class, no
# `remainder` (PR 32's program, the compiler's dot: `remainder` 24,
# `while` 16, `convolution` 9)
_GROUP_OPS_PR35 = {
    "small": {
        "add": 490, "and": 116, "bitcast": 66, "bitcast-convert": 89,
        "broadcast": 422, "clamp": 14, "compare": 109, "constant":
        449, "convert": 66, "convolution": 3, "copy": 23,
        "custom-call": 17, "dynamic-slice": 9, "dynamic-update-slice":
        16, "fusion": 92, "gather": 6, "get-tuple-element": 83,
        "is-finite": 87, "multiply": 92, "negate": 6, "or": 28, "pad":
        9, "parameter": 275, "reshape": 25, "scatter": 3, "select":
        177, "shift-left": 48, "shift-right-arithmetic": 48,
        "shift-right-logical": 69, "slice": 144, "subtract": 501,
        "transpose": 21, "tuple": 28, "while": 1},
    "north_star": {
        "add": 494, "and": 116, "bitcast": 61, "bitcast-convert": 89,
        "broadcast": 422, "clamp": 14, "compare": 109, "constant":
        451, "convert": 66, "convolution": 3, "copy": 29, "copy-done":
        10, "copy-start": 10, "custom-call": 18, "dynamic-slice": 9,
        "dynamic-update-slice": 16, "fusion": 94, "gather": 6,
        "get-tuple-element": 83, "is-finite": 87, "multiply": 92,
        "negate": 6, "or": 28, "pad": 9, "parameter": 285, "reduce":
        4, "reshape": 27, "scatter": 3, "select": 177, "shift-left":
        48, "shift-right-arithmetic": 48, "shift-right-logical": 69,
        "slice": 144, "slice-done": 4, "slice-start": 4, "subtract":
        501, "transpose": 21, "tuple": 28, "while": 1},
}


@pytest.mark.parametrize("shape", ["small", "north_star"])
def test_f64_group_program_is_op_for_op_what_it_was(one_chip, shape, request):
    """`group_chunk_loop` is shared with the mesh engine (PR 33) and
    every chain product compiles it anew: what one chip compiles must
    not move unseen, because `northstar.scf_f64`'s `peak_hbm_gib`
    counts the executable's text and `setup_s` every recompile."""
    if shape == "small":
        compiled = _small_group_program(one_chip)
    else:
        compiled, _, _ = request.getfixturevalue("ns_group_program")
    assert _op_counts(compiled.as_text()) == _GROUP_OPS_PR35[shape]


@pytest.mark.parametrize("body", ["xla", "xla_flat", "xla_group"])
def test_stack_body_scatter_adds_into_its_carry(body):
    """What the guard above holds the compiler to, read off the jaxpr
    (no topology): the loop body's only bin-shaped equations are the
    `scatter-add`s (one a width class of the grouped body) whose
    operand is the loop-carried C."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm

    nseg, m, n, k = 64, 5, 4, 3
    c = jax.ShapeDtypeStruct((nseg, m, n), jnp.float32)
    a = jax.ShapeDtypeStruct((9, m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((9, k, n), jnp.float32)
    flat = jax.ShapeDtypeStruct((3, 16), jnp.int32)
    grouped = jax.ShapeDtypeStruct((3, 16, 2), jnp.int32)
    narrow = jax.ShapeDtypeStruct((3, 16, 1), jnp.int32)
    idx = (jax.ShapeDtypeStruct((), jnp.int32), grouped, grouped, flat,
           narrow, narrow, flat) if body == "xla_group" else (flat,) * 3
    fn = {"xla": smm._stack_phases_xla, "xla_flat": smm._stack_phases_xla_flat,
          "xla_group": smm._stack_phases_group}[body]
    jaxpr = jax.make_jaxpr(fn)(
        c, a, b, *idx, jax.ShapeDtypeStruct((), jnp.float32))
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]
    if loop.primitive.name == "scan":
        step = loop.params["jaxpr"].jaxpr
    else:  # the grouped body: a `while` bounded by the live chunk count
        step = loop.params["body_jaxpr"].jaxpr
    (carry,) = [v for v in step.invars if v.aval.shape == c.shape]

    def eqns(jp):
        for e in jp.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    makers = [e for e in eqns(step)
              if any(getattr(v.aval, "shape", None) == c.shape
                     for v in e.outvars)]
    # one scatter-add a width class, each into what the last one left
    assert [e.primitive.name for e in makers] \
        == ["scatter-add"] * (2 if body == "xla_group" else 1), makers
    into = carry
    for e in makers:
        assert e.invars[0] is into
        assert e.params["indices_are_sorted"]
        (into,) = e.outvars


# ---------------------------------------------------------------------------
# The sparse mesh engine on the 2x2 grid (`northstar_2x2.scf_f64`): the
# programs a filtered f64 product runs, at the panels the deployment's
# pattern gives.
# ---------------------------------------------------------------------------

_MESH_R0 = 8


@pytest.fixture(scope="module")
def northstar_2x2_plan():
    """(cap_a, cap_b, cap_c, the grouped stacks) of the deployment on
    the 2x2 grid, from its pattern as `_build_mesh_plan` bins it:
    blocks go to devices cyclically, tick = the k parity that meets on
    a device, slots count a panel's blocks in key order, and
    `_fill_stacks` tiles the eight (device, tick) stacks."""
    import sys

    sys.path.insert(0, REPO)
    from benchmark import arithmetic
    from dbcsr_tpu.parallel import sparse_dist as sd
    from dbcsr_tpu.utils.rounding import bucket_size

    with open(os.path.join(
            REPO, "benchmark/configs/northstar_2x2_filtered.json")) as fh:
        cfg = json.load(fh)
    assert cfg["grid"] == [2, 2]
    nblk = len(arithmetic.expand_block_sizes(cfg["m"], cfg["blocks"]["m"]))
    rng = np.random.default_rng(cfg["pattern_seed"])
    pa = rng.random((nblk, nblk)) < cfg["occupancy"]["a"]
    pb = rng.random((nblk, nblk)) < cfg["occupancy"]["b"]
    assert (pa.sum(), pb.sum()) == (19115, 18989)  # the cell's operands
    # candidates (i, k, j), k-major
    a_of_k = [np.nonzero(pa[:, k])[0] for k in range(nblk)]
    b_of_k = [np.nonzero(pb[k])[0] for k in range(nblk)]
    i = np.concatenate([np.repeat(r, len(c)) for r, c in zip(a_of_k, b_of_k)])
    j = np.concatenate([np.tile(c, len(r)) for r, c in zip(a_of_k, b_of_k)])
    k = np.repeat(np.arange(nblk),
                  [len(r) * len(c) for r, c in zip(a_of_k, b_of_k)])
    reached = np.zeros((nblk, nblk), bool)
    reached[i, j] = True

    def panel_slots(there):
        """(slot of every block in its (row, column parity) panel, the
        fullest panel's count)."""
        r, c = np.nonzero(there)
        panel = (r % 2) * 2 + c % 2
        slot = np.zeros(there.shape, np.int64)
        slot[r, c] = sd._panel_slots(panel)
        return slot, int(np.bincount(panel).max())

    (a_slot, na), (b_slot, nb), (c_slot, nc) = map(panel_slots,
                                                   (pa, pb, reached))
    cap_a, cap_b, cap_c = map(bucket_size, (na, nb, nc))
    tick = (k % 2 - i % 2 - j % 2) % 2
    stack = ((i % 2) * 2 + j % 2) * 2 + tick
    # the runs PERF.md counts: a C block's candidates of one tick
    assert len(i) == 834386
    assert len(np.unique((stack * nblk + i) * nblk + j)) == 337410
    tiles = sd._fill_stacks(
        stack, a_slot[i, k], b_slot[k, j], c_slot[i, j], 8, cap_c,
        r0=_MESH_R0, pad_a=cap_a, pad_b=cap_b,
        chunk_groups=sd._stack_chunk_groups(_MESH_R0, 23, 23, 23,
                                            np.float64))
    return cap_a, cap_b, cap_c, tiles


def test_northstar_2x2_plan_launches_the_slots_that_hold_entries(
        northstar_2x2_plan):
    """Counts, not rates: until PR 33 every (device, tick) stack was
    49 152 rows of 8, 3 145 728 slots on the grid (786 432 a device a
    product) for these 834 386 candidates, fill 27%."""
    cap_a, cap_b, cap_c, tiles = northstar_2x2_plan
    # the panels the builder's chip run of PR 30 ran at
    assert (cap_a, cap_b, cap_c) == (5120, 5120, 49152)
    assert tiles.entries == 834386
    # runs of mean 2.47: a tick sees half a block's k range
    assert tiles.widths == (8, 4, 2, 1)
    assert tiles.groups == (27109, 116776, 101798, 91859)
    assert [ga.shape[1:] for ga, _, _ in tiles.tiles] == [
        (64, 64, 8), (64, 256, 4), (64, 224, 2), (64, 192, 1)]
    # 2 176 slots a chunk, 59-62 live chunks a (device, tick)
    assert tiles.live.tolist() == [60, 59, 60, 60, 60, 60, 62, 61]
    assert tiles.slots_launched == 482 * 2176 == 1048832
    assert tiles.entries / tiles.slots_launched > 0.79
    # a device's two ticks: 258 944-267 648 slots a product
    by_device = tiles.live.reshape(4, 2).sum(axis=1) * 2176
    assert by_device.tolist() == [258944, 261120, 261120, 267648]
    # every id names a row of the panel it gathers from, the pad row
    # included: the gathers promise that and check nothing
    for ga, gb, gc in tiles.tiles:
        assert 0 <= ga.min() and ga.max() == cap_a
        assert 0 <= gb.min() and gb.max() == cap_b
        assert 0 <= gc.min() and gc.max() == cap_c


@pytest.fixture(scope="module")
def mesh_shapes(v5e_2x2, northstar_2x2_plan):
    """The (1, 2, 2) mesh of the described chips and the engine's
    arguments on it, sharded as `_sparse_multiply_impl` places them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dbcsr_tpu.parallel.overlap import _HashableMesh

    cap_a, cap_b, cap_c, tiles = northstar_2x2_plan
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(1, 2, 2),
                ("kl", "pr", "pc"))

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    grid3 = ("kl", "pr", "pc")
    lead = (1, 2, 2, 2)  # (kl, pr, pc, ticks), as `_upload_stacks` lays them
    return {
        "mref": _HashableMesh(mesh), "cap_c": cap_c,
        "widths": tiles.widths,
        "a": arg((1, 2, 2, cap_a + 1, 23, 23), jnp.float64, *grid3),
        "b": arg((1, 2, 2, cap_b + 1, 23, 23), jnp.float64, *grid3),
        "stacks": (arg(lead, jnp.int32, *grid3),
                   tuple(tuple(arg(lead + x.shape[1:], jnp.int32, *grid3)
                               for x in tile) for tile in tiles.tiles)),
        "c_acc": arg((1, 2, 2, cap_c, 23, 23), jnp.float64, *grid3),
        "c_init": arg((2, 2, cap_c, 23, 23), jnp.float64, "pr", "pc"),
        "beta_fac": arg((2, 2, cap_c), jnp.float64, "pr", "pc"),
        "alpha": arg((), jnp.float64), "tick": arg((), jnp.int32),
    }


@pytest.mark.parametrize("program", ["tick", "run"])
def test_mesh_stack_program_of_northstar_2x2_compiles(mesh_shapes, program):
    """The split per-tick program and the fused serial one at the
    deployment's panels and grouped stacks, emulated f64: they compile,
    under the names the benchmark's `jit__stack_*` globs read, with the
    phase scopes of `acc/smm.py:group_chunk_loop` in their ops, and in
    its forms: ONE chunk `while` over the C panel, in it nothing
    panel-shaped but the sorted scatter-adds of `_accumulate_chunk`, one
    a width class (no `segment_sum`-style whole-panel add per chunk),
    no bounds compare and NaN fill on the gathers.  Temporaries a
    device: 2.26 GiB the tick (the arriving panel relaid to tiles and
    split into its two f32 halves), 1.48 GiB the fused program (3.13 /
    4.08 GiB in PR 30, with the gathered strips of 24 576 slots and the
    whole-panel arrays `segment_sum` made per chunk)."""
    import jax

    from dbcsr_tpu.parallel import sparse_dist as sd

    sh = mesh_shapes
    kw = dict(cap_c=sh["cap_c"], acc_name="float64", mesh_ref=sh["mref"],
              r0=_MESH_R0, dot_form="sliced")
    with jax.enable_x64(True):
        if program == "tick":
            lowered = sd._stack_tick_mesh.lower(
                sh["a"], sh["b"], sh["stacks"], sh["c_acc"], sh["tick"], **kw)
        else:
            lowered = sd._stack_run_mesh.lower(
                sh["a"], sh["b"], sh["stacks"], sh["c_init"], sh["alpha"],
                sh["beta_fac"], s=2, nticks=2, gather=False, **kw)
        compiled = lowered.compile()
    text = compiled.as_text()
    name = {"tick": "_stack_tick_mesh", "run": "_stack_run_mesh"}[program]
    assert f"HloModule jit_{name}" in text
    for scope in ("stk_gather", "stk_dot", "stk_accum", "stk_loop"):
        assert f"/{scope}/" in text, scope
    # the ring shift rides in the fused program and not in a split tick
    assert ("collective-permute" in text) == (program == "run")
    comps = _computations(text)
    panel = f"[{sh['cap_c']},23,23]"
    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))
    chunk_loops = [b for b in bodies
                   if any("stk_accum/scatter-add" in ln for ln in comps[b])]
    assert len(chunk_loops) == 1, sorted(chunk_loops)
    # the fused program's tick loop carries the panel around it
    over_panel = [b for b in bodies if any(panel in ln for ln in comps[b])]
    assert len(over_panel) == (1 if program == "tick" else 2), over_panel
    body = comps[chunk_loops[0]]
    made = [(ln, re.search(r" ([a-z][\w\-]*)\(", ln.split(" = ", 1)[1]))
            for ln in body if " = " in ln]
    makers = [ln for ln, op in made
              if panel in ln.split(" = ", 1)[1][:op.start()]
              and op.group(1) not in ("get-tuple-element", "parameter",
                                      "tuple")]
    assert len(makers) == len(sh["widths"]), [ln[:160] for ln in makers]
    assert all("stk_accum/scatter-add" in ln for ln in makers), \
        [ln[:400] for ln in makers]
    reached = [body] + [comps[c] for ln in body
                        for c in re.findall(r"calls=%?([\w.\-]+)", ln)]
    scatters = [ln for lines in reached for ln in lines
                if re.search(r" scatter\(", ln)]
    assert scatters and all("indices_are_sorted=true" in ln
                            for ln in scatters), scatters[:2]
    assert not any("constant(nan)" in ln for lines in reached
                   for ln in lines)
    # the panels are cut into slices once per tick, not per slot
    _assert_split_once_not_per_slot(comps, body, len(sh["widths"]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < {"tick": 2.5, "run": 1.7}[program] * 2 ** 30, temp


def test_mesh_shift_and_finish_of_northstar_2x2_compile(mesh_shapes):
    """The double-buffered pipeline's other two programs: the ring
    shift is the collective-permute of both sparse panels and nothing
    else; the finish holds no collective on a one-layer grid."""
    import jax

    from dbcsr_tpu.parallel import sparse_dist as sd

    sh = mesh_shapes
    with jax.enable_x64(True):
        shift = sd._mesh_shift_program.lower(
            sh["a"], sh["b"], s=2, mesh_ref=sh["mref"]).compile()
        finish = sd._mesh_finish_program.lower(
            sh["c_acc"], sh["c_init"], sh["alpha"], sh["beta_fac"],
            acc_name="float64", mesh_ref=sh["mref"]).compile()
    assert "collective-permute" in shift.as_text()
    assert shift.memory_analysis().temp_size_in_bytes < 2 ** 28
    assert "collective-permute" not in finish.as_text()
    assert "all-reduce" not in finish.as_text()


def _assert_collects_from_own_panels(compiled) -> None:
    """The forms of `_collect_bins` (PR 38) in a program compiled for
    the described 2x2 grid: under the name `layers/mesh_collect_s.json`
    globs; every device reads its own panel and the pieces cross the
    grid once, in an all-gather, with no all-reduce of zero-filled bins
    (until PR 38 the gather ran over the sharded buffer by global
    positions and the partitioner all-reduced every bin whole); every
    gather moves whole block rows of a 2-D operand (a gather of 3-D
    blocks fetches each element on its own: `acc/smm.py:_block_rows`);
    the bins leave whole on all four devices."""
    text = compiled.as_text()
    assert "HloModule jit__collect_bins" in text
    assert "all-gather" in text
    assert "all-reduce" not in text
    gathers = [ln for ln in text.splitlines() if re.search(r" gather\(", ln)]
    assert gathers
    for ln in gathers:
        assert re.search(r"= [a-z]\d+\[\d+,\d+\]\{1,0", ln), ln[:200]
        assert re.search(r"slice_sizes=\{1,\d+\}", ln), ln[:400]
    assert all(len(s.device_set) == 4 and s.is_fully_replicated
               for s in compiled.output_shardings)


def test_mesh_collect_of_northstar_2x2_compiles(mesh_shapes):
    """The collect at `northstar_2x2_filtered`'s shapes: C is full, 435^2
    blocks, 47 089 of 23 x 23 a device in a panel of `cap_c` 49 152,
    the ragged last row and column in bins of their own.  Temporaries a
    device 1.33 GiB (1 428 934 656 B compiled here, PR 38: the panel's
    and the bin's two f32 halves as rows); the program it replaced
    asked for 9.00 GiB (the gathered blocks a 24 x 128 tile each)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbcsr_tpu.parallel import sparse_dist as sd

    sh = mesh_shapes
    mesh = sh["mref"].val
    assert sh["cap_c"] == 49152

    def arg(shape, *spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(mesh, P(*spec)))

    with jax.enable_x64(True):
        compiled = sd._collect_bins.lower(
            sh["c_init"],
            tuple(arg((2, 2, n), "pr", "pc") for n in (16, 224, 224, 49152)),
            tuple(arg((n,)) for n in (16, 448, 448, 196608)),
            shapes=((18, 18), (18, 23), (23, 18), (23, 23)),
            mesh_ref=sh["mref"]).compile()
    _assert_collects_from_own_panels(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * 2 ** 30, temp


def test_chain_ops_of_h2o_ls_chain_compile_as_named_programs(one_chip):
    """The union add of X_new - X at `h2o_ls_chain`'s sizes (PR 32: X
    holds ~18 400 blocks of 23x23, H 17 000, the union in a bucket of
    up to 24 576), the scale and the diagonal shift: one module each, under
    the names `layers/chain_add_s.json` globs, the union add in place
    in its zeroed bin."""
    import jax
    import jax.numpy as jnp

    from dbcsr_tpu.ops import operations as ops

    f64 = jnp.float64
    with jax.enable_x64():
        bin_ = _shape(one_chip, (24576, 23, 23), f64)
        src = _shape(one_chip, (20480, 23, 23), f64)
        fac = _shape(one_chip, (), f64)

        def term(n):
            return (src, _shape(one_chip, (n,), jnp.int32),
                    _shape(one_chip, (n,), jnp.int32), fac)

        add = ops._add_union_bin.lower(bin_, (term(18248), term(19457)))
        compiled = add.compile()
        text = compiled.as_text()
        assert "HloModule jit__add_union_bin" in text
        assert "add_union" in text and "input_output_alias" in text
        # 1.66 GB of temporaries for a bin of 104 MB as values: the
        # gathered blocks and their products in tile-padded layout
        # (23x23 in 24x128 tiles, 5.8 times the values), as the eager
        # ops it replaced made them one by one; PERF.md section 7
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
        scale = ops._scale_bin.lower(bin_, fac).compile().as_text()
        assert "HloModule jit__scale_bin" in scale
        shift = ops._add_alpha_eye.lower(
            bin_, _shape(one_chip, (435,), jnp.int32), fac).compile()
        assert "HloModule jit__add_alpha_eye" in shift.as_text()


# ---------------------------------------------------------------------------
# The sign chain on the 2x2 grid (`h2o_ls_chain_2x2.sign_f64`, PR 37):
# every product has a pattern of its own, so the mesh programs run at
# several shapes a chain.  The two ends of the reference chain at full
# size (seed 1; `_build_mesh_plan` on the NumPy chain's operands, host
# only, PR 37): the largest product is X.T of the third step (466 300
# candidates, C born with 62 587 blocks), the smallest X.T of the last
# (20 383 candidates, T = 3I - X^2 all but diagonal: 2 048-bucket
# bins).  Seven of the fourteen products share the largest's panels and
# all but its chunk count (40 for 48).
# ---------------------------------------------------------------------------

_CHAIN_PRODUCTS = {
    # cap_a, cap_b, cap_c, (chunks, ((groups a chunk, width), ...)),
    # bucketed (18,18) / (18,23) / (23,18) / (23,23) bins of A, B and C,
    # and of C's bins the piece a device ships to the collect (PR 38:
    # a quarter of the blocks and a pad row, in the bucket above)
    "largest": (5120, 5120, 16384, (48, ((192, 8), (80, 4), (160, 2))),
                (16, 48, 48, 20480), (16, 48, 48, 20480),
                (16, 160, 160, 65536), (16, 96, 96, 16384)),
    "smallest": (5120, 640, 5120, (3, ((16, 8), (1952, 1))),
                 (16, 48, 48, 20480), (16, 16, 16, 2048),
                 (16, 56, 48, 20480), (16, 32, 28, 5120)),
}
_CHAIN_BIN_SHAPES = ((18, 18), (18, 23), (23, 18), (23, 23))
# temporaries a device, GiB, measured when compiled here for the
# described v5e:2x2 (PR 37), with a fifth of room: program -> product
_CHAIN_TEMP_GIB = {
    "tick": {"largest": 0.9, "smallest": 1.15},
    "finish": {"largest": 0.08, "smallest": 0.01},
    "collect": {"largest": 0.54, "smallest": 0.06},  # PR 38
    "assembly": {"largest": 1.15, "smallest": 0.1},
}


@pytest.fixture(scope="module")
def chain_mesh(v5e_2x2):
    from jax.sharding import Mesh

    return Mesh(np.array(v5e_2x2.devices).reshape(1, 2, 2),
                ("kl", "pr", "pc"))


@pytest.mark.parametrize("product", ["largest", "smallest"])
@pytest.mark.parametrize("program", ["tick", "finish", "collect",
                                     "assembly"])
def test_mesh_programs_of_the_sign_chain_compile(chain_mesh, program,
                                                 product):
    """The grid's programs at the two ends of `h2o_ls_chain_2x2`'s
    chain: the tick on the product's own class tiles, the finish on its
    C panel, the collect from every device's own panel into bins that
    every device holds whole (the pieces all-gathered: where X lives
    between two products), the assembly of B's panels from such
    replicated bins and the cut of the assembled buffer into the
    sharded panels.  Temporaries a device (GiB, compiled here, PR 37;
    largest / smallest): tick 0.751 / 0.936 (the smallest product's
    class of width 1 holds 1 952 groups a chunk), finish 0.065 / 0,
    collect 0.444 / 0.043 (PR 38: the panel and the bins as rows; 3.001
    / 0.938 until then, the gathered blocks a 24 x 128 tile each),
    assembly 0.938 / 0.077."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dbcsr_tpu.parallel import sparse_dist as sd
    from dbcsr_tpu.parallel.overlap import _HashableMesh

    (cap_a, cap_b, cap_c, (nchunk, classes), a_bins, b_bins, c_bins,
     c_pieces) = _CHAIN_PRODUCTS[product]
    grid3 = ("kl", "pr", "pc")

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(chain_mesh, P(*spec)))

    f64, i32 = jnp.float64, jnp.int32
    mref = _HashableMesh(chain_mesh)
    with jax.enable_x64(True):
        if program == "tick":
            lead = (1, 2, 2, 2)
            stacks = (arg(lead, i32, *grid3), tuple(
                (arg(lead + (nchunk, ch, w), i32, *grid3),
                 arg(lead + (nchunk, ch, w), i32, *grid3),
                 arg(lead + (nchunk, ch), i32, *grid3))
                for ch, w in classes))
            compiled = sd._stack_tick_mesh.lower(
                arg((1, 2, 2, cap_a + 1, 23, 23), f64, *grid3),
                arg((1, 2, 2, cap_b + 1, 23, 23), f64, *grid3), stacks,
                arg((1, 2, 2, cap_c, 23, 23), f64, *grid3), arg((), i32),
                cap_c=cap_c, acc_name="float64", mesh_ref=mref,
                r0=_MESH_R0, dot_form="sliced").compile()
            text = compiled.as_text()
            assert "HloModule jit__stack_tick_mesh" in text
            for scope in ("stk_gather", "stk_dot", "stk_accum"):
                assert f"/{scope}/" in text, scope
        elif program == "finish":
            compiled = sd._mesh_finish_program.lower(
                arg((1, 2, 2, cap_c, 23, 23), f64, *grid3),
                arg((2, 2, cap_c, 23, 23), f64, "pr", "pc"), arg((), f64),
                arg((2, 2, cap_c), f64, "pr", "pc"),
                acc_name="float64", mesh_ref=mref).compile()
            assert "all-reduce" not in compiled.as_text()
        elif program == "collect":
            compiled = sd._collect_bins.lower(
                arg((2, 2, cap_c, 23, 23), f64, "pr", "pc"),
                tuple(arg((2, 2, n), i32, "pr", "pc") for n in c_pieces),
                tuple(arg((n,), i32) for n in c_bins),
                shapes=_CHAIN_BIN_SHAPES, mesh_ref=mref).compile()
            _assert_collects_from_own_panels(compiled)
        else:
            compiled = sd._assemble_flat.lower(
                tuple(arg((n,) + shape, f64)
                      for n, shape in zip(b_bins, _CHAIN_BIN_SHAPES)),
                tuple(arg((n,), i32) for n in b_bins),
                tuple(arg((n,), i32) for n in b_bins),
                nflat=4 * (cap_b + 1), bm=23, bn=23,
                dtype_name="float64").compile()
            assert "HloModule jit__assemble_flat" in compiled.as_text()
            # every device holds the assembled buffer whole and cuts its
            # own panel out of it: a slice, no collective
            shape = (1, 2, 2, cap_b + 1, 23, 23)
            cut = sd._panel_cut_program(mref, shape, P(*grid3)).lower(
                arg((4 * (cap_b + 1), 23, 23), f64)).compile()
            assert not re.search(r"all-gather|all-reduce|collective-permute"
                                 r"|all-to-all", cut.as_text())
            assert cut.output_shardings.spec == P(*grid3)
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"CHAIN_TEMP {program} {product} {temp / 2 ** 30:.3f} GiB")
    assert temp < _CHAIN_TEMP_GIB[program][product] * 2 ** 30, temp
