"""Generator kind `rpa_chi`: cubic-scaling RPA's chi_PQ(i tau), one
memory-cut batch of one tau point a product, the batches in turn.

The polarisability of cubic-scaling RPA (CP2K `&RI_RPA` `&LOW_SCALING`,
Wilhelm, Seewald, Del Ben, Hutter, JCTC 12 (2016) 5851), the user
DBCSR's tensor layer was written for, is built at each imaginary time
tau from three block-sparse float64 contractions on atom blocks:

  1. M^occ_{P nu sigma}  = sum_lambda (P lambda nu) D^occ_{lambda sigma}
  2. M^virt_{Q nu sigma} = sum_lambda (Q lambda nu) D^virt_{lambda sigma}
  3. chi^(b)_{PQ} = sum_{nu sigma} M^occ_{P nu sigma} M^virt_{Q nu sigma}

A tau point does not fit at once, so it runs in c memory-cut batches
over sigma (`bounds_3`), each contraction inside `batched_contraction`
on its fresh result, so that the filter at `filter_eps` runs once, when
the batch finalizes (`dbt_batched_contract_init/finalize`).  One
product of the harness is one batch; the c batches are the distinct
products and the schedule takes them in turn, closed loop, one client:
an RPA code waits for each batch before the next.

`Deployment.run_batch` calls `dbcsr_tpu.tensor` (`create_tensor`,
`contract`, `batched_contraction`) and nothing else of the program:
`contract` -> `tas_multiply` -> `mm.multiply`, the normal path.
Everything else here imports nothing of `dbcsr_tpu`: a box of water
(`place_atoms`, positions from the configuration's `pattern_seed`), the
three-centre integrals (`three_centre`, values from `--seed`), the
density matrices D(tau) of a seeded model Hamiltonian
(`density_matrices`), and the plain reference (`reference_batch`,
NumPy), which applies the program's semantics to the same blocks: every
product of stored blocks (inside a batch nothing is pruned, the filter
is deferred), then the blocks of M^occ, M^virt and chi under
`filter_eps` dropped.  Its surviving stacks are the flops and bytes the
harness counts, and `check` holds chi's sampled block rows to it.

The recipe (blocks, cut radii, the model H, tau, the batches) is the
configuration file's `recipe`, and its size `m`, the RI functions, is
the box: m / 84 molecules.  `Deployment` takes the recipe and the
molecule count, so the smoke leg and the tests run the same deployment
at fewer molecules.
"""

import dataclasses
import itertools
import time

import numpy as np

_EPS64 = float(np.finfo(np.float64).eps)


def _offsets(nel) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(nel)]).astype(np.int64)


def _morton_order(points: np.ndarray) -> np.ndarray:
    """Order of ``points`` in the unit cube along a Morton curve."""
    q = np.minimum((points * 1024).astype(np.int64), 1023)
    code = np.zeros(len(points), np.int64)
    for bit in range(10):
        for d in range(3):
            code |= ((q[:, d] >> bit) & 1) << (3 * bit + d)
    return np.argsort(code, kind="stable")


# ------------------------------------------------------------- the box
@dataclasses.dataclass
class Box:
    """Atoms of a periodic cube of water, in Morton order."""

    side: float            # nm
    pos: np.ndarray        # (natoms, 3) nm, inside [0, side)
    kind: np.ndarray       # (natoms,) index into the molecule's atoms
    ao: np.ndarray         # (natoms,) AO functions of each atom
    ri: np.ndarray         # (natoms,) RI functions of each atom

    @property
    def natoms(self) -> int:
        return len(self.kind)

    def distances(self) -> np.ndarray:
        """(natoms, natoms) minimum-image distances, nm."""
        delta = self.pos[:, None, :] - self.pos[None, :, :]
        delta -= self.side * np.round(delta / self.side)
        return np.sqrt((delta ** 2).sum(axis=2))


def _unit(v):
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def place_atoms(molecules: int, density: float, pattern_seed: int, *,
                oh: float, hoh_degrees: float, ao_per_atom, ri_per_atom
                ) -> Box:
    """``molecules`` waters at ``density`` molecules a nm^3 in a cube:
    centres uniform from ``pattern_seed``, O at the centre, the two H at
    ``oh`` nm in seeded directions ``hoh_degrees`` apart; the molecules
    in the Morton order of their centres,
    the atoms of a molecule together, O first, as a CP2K coordinate
    file lists them (so a batch of whole molecules holds every atom
    kind in the same proportion)."""
    side = (molecules / float(density)) ** (1.0 / 3.0)
    rng = np.random.default_rng(int(pattern_seed))
    centre = rng.random((molecules, 3)) * side
    u = _unit(rng.standard_normal((molecules, 3)))
    w = rng.standard_normal((molecules, 3))
    w = _unit(w - (w * u).sum(axis=1, keepdims=True) * u)
    theta = np.deg2rad(hoh_degrees)
    v = np.cos(theta) * u + np.sin(theta) * w
    per = len(ao_per_atom)
    pos = np.empty((molecules, per, 3))
    pos[:, 0] = centre
    pos[:, 1] = centre + oh * u
    pos[:, 2:] = (centre + oh * v)[:, None, :]
    pos = np.mod(pos[_morton_order(centre / side)], side)
    kind = np.tile(np.arange(per), molecules)
    return Box(side, pos.reshape(-1, 3), kind,
               np.asarray(ao_per_atom, np.int64)[kind],
               np.asarray(ri_per_atom, np.int64)[kind])


# ---------------------------------------------------------- block tensors
@dataclasses.dataclass
class BlockMatrix:
    """A block-sparse matrix on the host, in the layout the program
    stores it: entries in (row, col) order, each block row-major at
    ``offsets`` in ``flat``."""

    row_sizes: np.ndarray
    col_sizes: np.ndarray
    rows: np.ndarray
    cols: np.ndarray
    offsets: np.ndarray  # one more than entries
    flat: np.ndarray

    @property
    def nblks(self) -> int:
        return len(self.rows)

    @property
    def keys(self) -> np.ndarray:
        return self.rows * len(self.col_sizes) + self.cols

    def block(self, e: int) -> np.ndarray:
        m, n = self.row_sizes[self.rows[e]], self.col_sizes[self.cols[e]]
        return self.flat[self.offsets[e]:self.offsets[e + 1]].reshape(m, n)

    def norms(self) -> np.ndarray:
        sq = np.add.reduceat(self.flat ** 2, self.offsets[:-1]) \
            if self.nblks else np.zeros(0)
        return np.sqrt(sq)

    def by_shape(self):
        """(rows, cols, (N, bm, bn) array) per distinct block shape."""
        bm = self.row_sizes[self.rows]
        bn = self.col_sizes[self.cols]
        for m, n in sorted(set(zip(bm.tolist(), bn.tolist()))):
            sel = np.nonzero((bm == m) & (bn == n))[0]
            idx = self.offsets[sel][:, None] + np.arange(m * n)[None, :]
            yield (self.rows[sel], self.cols[sel],
                   self.flat[idx].reshape(len(sel), m, n))

    def elements(self) -> int:
        return int(self.offsets[-1])


def three_centre(box: Box, dist: np.ndarray, seed: int, *, cutoff: float,
                 decay: float) -> BlockMatrix:
    """(P lambda nu) as the matrix of rows (P, nu) and columns lambda:
    a block where |r_lambda - r_nu| <= cutoff and P lies within cutoff
    of both, valued G exp(-(d_lambda_nu + d_P_lambda + d_P_nu) / decay)
    with G Gaussian of unit Frobenius norm.  Element (p, v, l) of the
    block sits at row p * nu_size + v, column l (the program's nd->2d
    mapping of row dims (P, nu), column dim lambda)."""
    n = box.natoms
    near = dist <= cutoff
    # (P, nu, lambda) in row-major matrix key order: row P*n + nu, col lambda
    p, v, lam = np.nonzero(near[:, :, None] & near[:, None, :]
                           & near[None, :, :])
    m = box.ri[p] * box.ao[v]
    k = box.ao[lam]
    offsets = _offsets(m * k)
    rng = np.random.default_rng([int(seed), 0])
    flat = rng.standard_normal(int(offsets[-1]))
    owner = np.repeat(np.arange(len(p)), m * k)
    scale = np.exp(-(dist[lam, v] + dist[p, lam] + dist[p, v]) / decay)
    norm = np.sqrt(np.add.reduceat(flat ** 2, offsets[:-1]))
    flat *= (scale / norm)[owner]
    ri_ao = np.repeat(box.ri, n) * np.tile(box.ao, n)
    return BlockMatrix(ri_ao, box.ao.copy(), p * n + v, lam, offsets, flat)


def atom_hamiltonian(box: Box, dist: np.ndarray, seed: int, *,
                     occupied_per_atom, coupling: float, decay: float,
                     virtual_width: float) -> np.ndarray:
    """The dense model H on the AO blocking (the chain's recipe on atom
    blocks): diagonal block of an atom = diag(-1 x its occupied, the
    rest evenly from +1 to +virtual_width); block (i, j), i != j, =
    coupling * exp(-d_ij / decay) * G_ij with G_ij Gaussian of unit
    Frobenius norm, H_ji = H_ij^T.  Returns (H, occupied count)."""
    off = _offsets(box.ao)
    h = np.zeros((off[-1], off[-1]))
    rng = np.random.default_rng([int(seed), 1])
    occ_of = np.asarray(occupied_per_atom, np.int64)[box.kind]
    for i in range(box.natoms):
        si = int(box.ao[i])
        rank = (np.arange(si) - occ_of[i]) / max(1, si - occ_of[i] - 1)
        h[off[i]:off[i + 1], off[i]:off[i + 1]] = np.diag(np.where(
            np.arange(si) < occ_of[i], -1.0,
            1.0 + (virtual_width - 1.0) * rank))
        for j in range(i + 1, box.natoms):
            g = rng.standard_normal((si, int(box.ao[j])))
            g *= coupling * np.exp(-dist[i, j] / decay) / np.linalg.norm(g)
            h[off[i]:off[i + 1], off[j]:off[j + 1]] = g
            h[off[j]:off[j + 1], off[i]:off[i + 1]] = g.T
    return h, int(occ_of.sum())


def block_matrix_of(dense: np.ndarray, sizes, keep_eps) -> BlockMatrix:
    """The atom blocks of ``dense`` whose norm passes the filter
    (||blk||^2 >= eps^2, `dbcsr_filter`'s rule), in key order."""
    sizes = np.asarray(sizes, np.int64)
    off = _offsets(sizes)
    nb = len(sizes)
    blocks = [dense[off[i]:off[i + 1], off[j]:off[j + 1]]
              for i in range(nb) for j in range(nb)]
    sq = np.asarray([float((b ** 2).sum()) for b in blocks])
    kept = np.nonzero(sq >= float(keep_eps) ** 2)[0]
    flat = np.concatenate([blocks[e].ravel() for e in kept]) \
        if len(kept) else np.zeros(0)
    nel = sizes[kept // nb] * sizes[kept % nb]
    return BlockMatrix(sizes, sizes, kept // nb, kept % nb, _offsets(nel),
                       flat)


def density_matrices(h: np.ndarray, nocc: int, tau: float):
    """D^occ(tau) = C_o e^{eps_o tau} C_o^T, D^virt(tau) = C_v e^{-eps_v
    tau} C_v^T, energies measured from the middle of the gap."""
    e, c = np.linalg.eigh(h)
    mid = 0.5 * (e[nocc - 1] + e[nocc])
    co, cv = c[:, :nocc], c[:, nocc:]
    docc = (co * np.exp((e[:nocc] - mid) * tau)) @ co.T
    dvirt = (cv * np.exp(-(e[nocc:] - mid) * tau)) @ cv.T
    return docc, dvirt, {"gap": float(e[nocc] - e[nocc - 1]),
                         "homo": float(e[nocc - 1] - mid),
                         "band": [float(e[0] - mid), float(e[-1] - mid)]}


# ------------------------------------------------------- the reference
@dataclasses.dataclass
class Panel:
    """A result of the reference as a dense panel of its stored block
    rows x block columns (blocks the filter dropped, or never born,
    are zero), with what a BlockMatrix of its kept blocks needs."""

    rows: np.ndarray      # block row of each panel row block
    cols: np.ndarray      # block column of each panel column block
    r_off: np.ndarray     # element offsets of the row blocks
    c_off: np.ndarray
    data: np.ndarray
    kept: np.ndarray      # (len(rows), len(cols)) bool
    mat: object           # BlockMatrix of the kept blocks, or None


def _ranges(off, idx) -> np.ndarray:
    """Concatenated element ranges [off[i], off[i+1]) of ``idx``."""
    idx = np.asarray(idx, np.int64)
    if not len(idx):
        return np.zeros(0, np.int64)
    lens = off[idx + 1] - off[idx]
    starts = np.repeat(off[idx] - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                       lens)
    return starts + np.arange(int(lens.sum()))


def _born_by_shape(born, m_sizes, n_sizes) -> tuple:
    """({(m, n): born blocks of that shape}, born elements) of a pattern
    whose rows and columns have the sizes given."""
    bi, bj = np.nonzero(born)
    shapes, counts = np.unique(np.stack([m_sizes[bi], n_sizes[bj]]),
                               axis=1, return_counts=True)
    return ({(int(m), int(n)): int(c) for (m, n), c in zip(shapes.T, counts)},
            int((m_sizes[bi] * n_sizes[bj]).sum()))


def _finish(data, rows, cols, row_sizes, col_sizes, r_off, c_off, born,
            eps, stacks, *, blocks: bool = True, counted=None) -> tuple:
    """Drop the born blocks of a dense panel under ``eps`` (zeroing
    them in the panel) and count: (Panel, info).  The kept blocks are
    gathered into the Panel's BlockMatrix where ``blocks`` asks.
    ``counted``: the `_born_by_shape` of the whole result where the
    panel holds only some of its rows (it counts the panel's)."""
    pm = row_sizes[rows]
    pn = col_sizes[cols]
    if data.size:
        sq = np.add.reduceat(np.add.reduceat(np.square(data), r_off[:-1],
                                             axis=0), c_off[:-1], axis=1)
    else:
        sq = np.zeros(born.shape)
    kept = born & (sq >= float(eps) ** 2)
    dropped = born & ~kept
    if dropped.any():
        data *= np.repeat(np.repeat(~dropped, pm, axis=0), pn, axis=1)
    sizes = pm[:, None] * pn[None, :]
    mat = None
    if blocks:
        ki, kj = np.nonzero(kept)
        flat = np.concatenate(
            [data[r_off[i]:r_off[i + 1], c_off[j]:c_off[j + 1]].ravel()
             for i, j in zip(ki, kj)]) if len(ki) else np.zeros(0)
        mat = BlockMatrix(row_sizes, col_sizes, rows[ki], cols[kj],
                          _offsets(sizes[ki, kj]), flat.astype(np.float64))
    c_blocks, born_elements = counted or _born_by_shape(born, pm, pn)
    stack_list = [(m, n, k, e, c_blocks[(m, n)])
                  for (m, n, k), e in sorted(stacks.items()) if e]
    info = {"born": int(sum(c_blocks.values())),
            "dropped": int(dropped.sum()), "born_elements": born_elements,
            "kept": int(kept.sum()), "kept_elements": int(sizes[kept].sum()),
            "flops": sum(2 * m * n * k * e for m, n, k, e, _ in stack_list),
            "stacks": stack_list}
    return Panel(rows, cols, r_off, c_off, data, kept, mat), info


def _size_counts(present, sizes):
    """{size: (rows,) count of the present columns of that size}."""
    return {int(s): (present[:, sizes == s]).sum(axis=1).astype(np.int64)
            for s in np.unique(sizes)}


def step_m(t3: BlockMatrix, d: BlockMatrix, sigma, eps, *,
           compute=np.float64):
    """M_{(P nu), sigma} = sum_lambda (P lambda nu) D_{lambda sigma}
    for sigma in ``sigma`` (block columns): every product of stored
    blocks, one dense product per stored (P, nu) row of the
    three-centre matrix, then blocks under ``eps`` dropped.  Returns
    (Panel of rows (P, nu) x columns sigma, info)."""
    sigma = np.asarray(sorted(sigma), np.int64)
    ao = d.row_sizes
    ao_off = _offsets(ao)
    s_sizes = ao[sigma]
    s_off = _offsets(s_sizes)
    col_of = np.full(len(ao), -1)
    col_of[sigma] = np.arange(len(sigma))
    present = np.zeros((len(ao), len(sigma)), bool)
    dpanel = np.zeros((ao_off[-1], s_off[-1]), compute)
    for e in np.nonzero(col_of[d.cols] >= 0)[0]:
        r, c = d.rows[e], col_of[d.cols[e]]
        present[r, c] = True
        dpanel[ao_off[r]:ao_off[r + 1], s_off[c]:s_off[c + 1]] = d.block(e)
    prow, start = np.unique(t3.rows, return_index=True)
    start = np.append(start, t3.nblks)
    pm = t3.row_sizes[prow]
    r_off = _offsets(pm)
    out = np.zeros((r_off[-1], s_off[-1]), compute)
    born = np.zeros((len(prow), len(sigma)), bool)
    for r in range(len(prow)):
        ents = np.arange(start[r], start[r + 1])
        lams = t3.cols[ents]
        live = present[lams].any(axis=0)
        if not live.any():
            continue
        born[r] = live
        m = int(pm[r])
        x = np.concatenate([t3.flat[t3.offsets[e]:t3.offsets[e + 1]]
                            .reshape(m, -1) for e in ents], axis=1)
        out[r_off[r]:r_off[r + 1]] = x.astype(compute) @ dpanel[
            _ranges(ao_off, lams)]
    # entries by (m, n, k): for each stored (P lambda nu), the sigma of
    # each size that D's row lambda stores
    by_n = _size_counts(present, s_sizes)
    ent_m = t3.row_sizes[t3.rows]
    ent_k = ao[t3.cols]
    stacks: dict = {}
    for n, per_lam in by_n.items():
        cnt = per_lam[t3.cols]
        for m in np.unique(ent_m):
            for k in np.unique(ent_k):
                sel = (ent_m == m) & (ent_k == k)
                stacks[(int(m), n, int(k))] = int(cnt[sel].sum())
    return _finish(out, prow, sigma, t3.row_sizes, ao, r_off, s_off, born,
                   eps, stacks, blocks=False)


def step_chi(mo: Panel, mv: Panel, ri, ao, eps, *, rows=None,
             compute=np.float64):
    """chi_{PQ} = sum_{nu sigma} M^occ_{(P nu) sigma} M^virt_{(Q nu)
    sigma}: every product of stored blocks (P, (nu sigma)) x ((nu
    sigma), Q), one dense product per nu over the batch's sigma, then
    blocks under ``eps`` dropped.  ``mo`` and ``mv`` are `step_m`'s
    panels (rows (P, nu), the same sigma columns).  ``rows``: the atoms
    P whose block rows of chi are computed (every one where None); the
    stacks count every row, from the kept patterns of M alone."""
    ri = np.asarray(ri, np.int64)
    natoms = len(ri)
    ri_off = _offsets(ri)
    prow = np.arange(natoms) if rows is None else np.unique(rows)
    at = np.full(natoms, -1)
    at[prow] = np.arange(len(prow))
    r_off = _offsets(ri[prow])
    out = np.zeros((r_off[-1], ri_off[-1]), compute)
    s_sizes = ao[mo.cols]
    width = int(s_sizes.sum())

    def side(pan, nu, wanted):
        """(atoms P of row (P, nu), P in ``wanted``, and their rows
        (p, (v s)) stacked)."""
        sel = np.nonzero(pan.rows % natoms == nu)[0]
        sel = sel[wanted[pan.rows[sel] // natoms]]
        ps = pan.rows[sel] // natoms
        if not len(sel):
            return ps, None
        return ps, np.concatenate([
            pan.data[pan.r_off[i]:pan.r_off[i + 1]].reshape(
                ri[p], int(ao[nu]) * width) for i, p in zip(sel, ps)])

    # the kept pattern by (P, (nu, sigma)) on both sides: chi's born
    # blocks and the entries of every (m, n, k)
    ko = np.zeros((natoms, natoms, len(mo.cols)), bool)
    kv = np.zeros_like(ko)
    ko[mo.rows // natoms, mo.rows % natoms] = mo.kept
    kv[mv.rows // natoms, mv.rows % natoms] = mv.kept
    ko = ko.reshape(natoms, -1)
    kv = kv.reshape(natoms, -1)
    born = (ko.astype(np.float32) @ kv.T.astype(np.float32)) > 0
    ksize = (ao[:, None] * s_sizes[None, :]).reshape(-1)
    stacks: dict = {}
    for m in np.unique(ri):
        for n in np.unique(ri):
            cnt = ko[ri == m].sum(axis=0) * kv[ri == n].sum(axis=0)
            for k in np.unique(ksize):
                stacks[(int(m), int(n), int(k))] = int(
                    cnt[ksize == k].sum())
    every = np.ones(natoms, bool)
    for nu in range(natoms):
        pa, a = side(mo, nu, at >= 0)
        qb, b = side(mv, nu, every)
        if a is None or b is None:
            continue
        out[np.ix_(_ranges(r_off, at[pa]), _ranges(ri_off, qb))] += \
            a.astype(compute) @ b.astype(compute).T
    # the program computes every row: its born blocks, not the panel's
    return _finish(out, prow, np.arange(natoms), ri, ri, r_off, ri_off,
                   born[prow], eps, stacks,
                   counted=_born_by_shape(born, ri, ri))


@dataclasses.dataclass
class Batch:
    chi: BlockMatrix  # the computed block rows of chi, filtered
    infos: list       # per contraction: born, dropped, flops, stacks
    m_blocks: tuple   # blocks M^occ and M^virt keep after the filter
    m_elements: int   # their elements together
    seconds: float


def reference_batch(t3: BlockMatrix, docc: BlockMatrix, dvirt: BlockMatrix,
                    sigma, ri, eps, *, rows=None,
                    compute=np.float64) -> Batch:
    """The three contractions of one batch in plain NumPy, block by
    block over the contracted index (one dense product per stored
    (P, nu) row for M, one per nu for chi), with the program's filters:
    none inside the batch, ``eps`` on each result.  chi's block rows
    are those of the atoms ``rows`` (every one where None)."""
    t0 = time.perf_counter()
    mo, io = step_m(t3, docc, sigma, eps, compute=compute)
    mv, iv = step_m(t3, dvirt, sigma, eps, compute=compute)
    chi, ic = step_chi(mo, mv, ri, docc.row_sizes, eps, rows=rows,
                       compute=compute)
    return Batch(chi.mat, [io, iv, ic], (io["kept"], iv["kept"]),
                 io["kept_elements"] + iv["kept_elements"],
                 time.perf_counter() - t0)


def batch_atoms(natoms: int, batches: int, b: int,
                per_molecule: int = 3) -> tuple:
    """(first, last) sigma atom of batch b: ``batches`` contiguous runs
    of whole molecules in the Morton order, as even as they come."""
    molecules = natoms // per_molecule
    per = -(-molecules // batches)
    return (b * per * per_molecule,
            min((b + 1) * per, molecules) * per_molecule - 1)


def chi_tolerance(ao, nk: int) -> float:
    """Largest elementwise error of chi against the NumPy chi, relative
    to max|chi|: a hundred times the bound of a float64 sum of ``nk``
    (nu, sigma) blocks, each a dot as deep as the largest nu_size *
    sigma_size, bounded by 2 eps sqrt((k + 1)(nk + 1)) (the bound the
    benchmark's `arithmetic.reference_tolerance` writes).  A hundred:
    the roundings of M^occ and M^virt enter chi as data, each through
    such a sum, and chi's terms cancel, so max|chi| lies under the sums
    of |terms| the bound is written for.  On a v5e at 32 molecules the
    program's chi read up to 6 times the bare bound (1.2e-12 against
    2.0e-13, 24 batches over three seeds), so this leaves 16 times over
    the largest reading; the batch computed in float32 reads four
    decades over it, and one element of chi off by a part in 1e10 fails
    it."""
    k = int(np.max(ao)) ** 2
    return 100.0 * max(2.0 * _EPS64 * float((k + 1) * (nk + 1)) ** 0.5,
                       4.0 * _EPS64 * float(k + 1) ** 0.5)


def compare_chi(ref: BlockMatrix, keys_got, blocks_got: dict,
                tol: float) -> dict:
    """The program's chi against the reference's: the whole pattern (a
    block the filter drops must be absent on both sides), then every
    block the reference keeps, elementwise, relative to max|chi_ref|.
    ``blocks_got`` maps (row, col) to the program's block."""
    same_pattern = bool(np.array_equal(np.sort(keys_got), ref.keys))
    scale = max(float(np.max(np.abs(ref.flat), initial=0.0)), 1e-300)
    err, finite, missing, bins = 0.0, True, 0, set()
    for e in range(ref.nblks):
        got = blocks_got.get((int(ref.rows[e]), int(ref.cols[e])))
        if got is None:
            missing += 1
            continue
        got = np.asarray(got, np.float64)
        if not np.all(np.isfinite(got)):
            finite = False
            continue
        want = ref.block(e)
        err = max(err, float(np.max(np.abs(got - want))))
        bins.add(want.shape)
    rel = err / scale
    return {"same_pattern": same_pattern, "missing": missing,
            "finite": finite, "rel_err": rel, "tol": tol,
            "compared_blocks": ref.nblks - missing,
            "bins": sorted(bins),
            "ok": bool(same_pattern and not missing and finite
                       and rel <= tol)}




# ---------------------------------------------------------- the deployment
class Deployment:
    """The configuration's ``recipe`` on a box of ``molecules`` (positions
    from ``pattern_seed``, values from ``seed``): (P lambda nu), D^occ
    and D^virt built on the host and staged as `dbcsr_tpu.tensor`
    tensors, and its memory-cut batches over sigma."""

    def __init__(self, recipe: dict, molecules: int, pattern_seed: int,
                 seed: int, *, dtype: str = "float64"):
        import dbcsr_tpu.tensor as dtt

        r = recipe
        self.recipe, self.dtype = r, dtype
        self.filter_eps = float(r["filter_eps"])
        t0 = time.perf_counter()
        self.box = place_atoms(
            int(molecules), r["density"], pattern_seed,
            oh=r["oh_distance"], hoh_degrees=r["hoh_angle"],
            ao_per_atom=r["ao_per_atom"], ri_per_atom=r["ri_per_atom"])
        dist = self.box.distances()
        self.t3 = three_centre(self.box, dist, seed, cutoff=r["cutoff"],
                               decay=r["decay"])
        h, nocc = atom_hamiltonian(
            self.box, dist, seed, occupied_per_atom=r["occupied_per_atom"],
            coupling=r["coupling"], decay=r["h_decay"],
            virtual_width=r["virtual_width"])
        docc, dvirt, self.spectrum = density_matrices(h, nocc, r["tau"])
        self.docc = block_matrix_of(docc, self.box.ao, self.filter_eps)
        self.dvirt = block_matrix_of(dvirt, self.box.ao, self.filter_eps)
        self.batches = min(int(r["batches"]), int(molecules))
        self.host_s = time.perf_counter() - t0

        ao, ri = self.box.ao, self.box.ri
        self.tensor_3c = dtt.create_tensor(
            "B_Plamnu", [ri, ao, ao], row_dims=(0, 2), col_dims=(1,),
            dtype=dtype)
        self.tensor_docc = dtt.create_tensor(
            "D_occ", [ao, ao], row_dims=(0,), col_dims=(1,), dtype=dtype)
        self.tensor_dvirt = dtt.create_tensor(
            "D_virt", [ao, ao], row_dims=(0,), col_dims=(1,), dtype=dtype)
        for tensor, mat in ((self.tensor_3c, self.t3),
                            (self.tensor_docc, self.docc),
                            (self.tensor_dvirt, self.dvirt)):
            for rows, cols, data in mat.by_shape():
                tensor.matrix.put_blocks(rows, cols, data.astype(dtype))
            tensor.finalize()

    def describe(self) -> dict:
        n = self.box.natoms
        norms = self.t3.norms()
        return {"molecules": n // 3, "atoms": n, "box_nm": self.box.side,
                "ao": int(self.box.ao.sum()), "ri": int(self.box.ri.sum()),
                "batches": self.batches, "b3_blocks": self.t3.nblks,
                "b3_share_of_triples": self.t3.nblks / float(n) ** 3,
                "b3_bytes": 8 * self.t3.elements(),
                "b3_norm_range": [float(norms.min()), float(norms.max())],
                "docc_share": self.docc.nblks / float(n) ** 2,
                "dvirt_share": self.dvirt.nblks / float(n) ** 2,
                "spectrum": self.spectrum, "filter_eps": self.filter_eps,
                "host_s": self.host_s}

    def sigma(self, b: int) -> range:
        lo, hi = batch_atoms(self.box.natoms, self.batches, b)
        return range(lo, hi + 1)

    def run_batch(self, b: int):
        """Batch ``b`` of the tau point through the tensor layer; returns
        (chi^(b), the flops the program counts)."""
        import dbcsr_tpu.tensor as dtt

        sigma = self.sigma(b)
        ao, ri = self.box.ao, self.box.ri
        eps = self.filter_eps
        flops = 0
        m = {}
        for name, d in (("M_occ", self.tensor_docc),
                        ("M_virt", self.tensor_dvirt)):
            m[name] = dtt.create_tensor(name, [ri, ao, ao], row_dims=(0, 1),
                                        col_dims=(2,), dtype=self.dtype)
            with dtt.batched_contraction(m[name]):
                flops += dtt.contract(
                    1.0, self.tensor_3c, d, 0.0, m[name],
                    contract_a=(1,), notcontract_a=(0, 2),
                    contract_b=(0,), notcontract_b=(1,),
                    map_1=(0, 1), map_2=(2,), filter_eps=eps,
                    bounds_3=[(sigma[0], sigma[-1])])
        chi = dtt.create_tensor("chi", [ri, ri], row_dims=(0,),
                                col_dims=(1,), dtype=self.dtype)
        with dtt.batched_contraction(chi):
            flops += dtt.contract(
                1.0, m["M_occ"], m["M_virt"], 0.0, chi,
                contract_a=(1, 2), notcontract_a=(0,),
                contract_b=(1, 2), notcontract_b=(0,),
                map_1=(0,), map_2=(1,), filter_eps=eps)
        return chi, int(flops)

    def reference(self, b: int, *, rows=None, compute=np.float64) -> Batch:
        """The NumPy batch on the same blocks, chi's block rows those of
        the atoms ``rows`` (every one where None)."""
        return reference_batch(self.t3, self.docc, self.dvirt, self.sigma(b),
                               self.box.ri, self.filter_eps, rows=rows,
                               compute=compute)

    def tolerance(self, b: int) -> float:
        return chi_tolerance(self.box.ao, self.box.natoms * len(self.sigma(b)))

    def sample_rows(self, seed: int, picks: int = 8) -> np.ndarray:
        """Atoms P whose block rows of chi are checked: one of each RI
        block size (so every (m, n) bin of chi is held to NumPy whatever
        the seed), then seeded picks up to ``picks``."""
        rng = np.random.default_rng([int(seed), 2])
        ri = self.box.ri
        rows = {int(rng.choice(np.nonzero(ri == s)[0])) for s in np.unique(ri)}
        order = rng.permutation(len(ri))
        for p in order[:max(0, picks - len(rows))]:
            rows.add(int(p))
        return np.asarray(sorted(rows), np.int64)

    def check(self, b: int, chi, rows=None, ref: Batch = None) -> dict:
        """The program's chi^(b) against the NumPy batch on the block
        rows of ``rows`` (every row where None): their pattern and every
        block the reference keeps there."""
        ref = ref if ref is not None else self.reference(b, rows=rows)
        nb = self.box.natoms
        rows_got, cols_got = chi.matrix.entry_coords()
        rows_got = np.asarray(rows_got, np.int64)
        if rows is not None:
            keep = np.isin(rows_got, rows)
            rows_got, cols_got = rows_got[keep], np.asarray(cols_got)[keep]
        keys_got = rows_got * nb + np.asarray(cols_got, np.int64)
        fetched = chi.matrix.get_blocks(ref.chi.rows, ref.chi.cols)
        got = {(int(p), int(q)): blk for p, q, blk in
               zip(ref.chi.rows, ref.chi.cols, fetched) if blk is not None}
        result = compare_chi(ref.chi, keys_got, got, self.tolerance(b))
        result.update(chi_blocks=int(len(keys_got)),
                      chi_blocks_reference=int(ref.chi.nblks),
                      reference_s=ref.seconds)
        return result


def batch_stacks(infos) -> list:
    """The three contractions' (m, n, k, entries, c_blocks) as one
    product's: M^occ and M^virt fill C bins of the same (m, n), each
    its own, so such a bin's c_blocks is the sum of theirs (what
    `arithmetic.fused_stack_bytes` reads from a bin's first stack)."""
    c_blocks: dict = {}
    for info in infos:
        for m, n, _, _, cb in {(s[0], s[1], 0, 0, s[4]) for s in
                               info["stacks"]}:
            c_blocks[(m, n)] = c_blocks.get((m, n), 0) + cb
    return [(m, n, k, e, c_blocks[(m, n)])
            for info in infos for m, n, k, e, _ in info["stacks"]]


# ------------------------------------------------------------ the harness
class Generator:
    """The harness's side: the configuration's deployment, its c
    batches the distinct products, taken in turn."""

    def __init__(self, bench, config: dict, traffic: dict, seed: int,
                 devices: list):
        self.bench = bench
        self.config, self.traffic = config, traffic
        self.seed, self.devices = int(seed), devices
        self.dtype = traffic["dtype"]
        if list(config["grid"]) != [1, 1]:
            raise ValueError("rpa_chi runs on one chip: grid [1, 1]")
        self._refs: dict = {}

    def molecules(self) -> int:
        """The box: m RI functions, a water's (O, H, H) blocks each."""
        return max(1, int(self.config["m"]) // int(
            sum(self.config["recipe"]["ri_per_atom"])))

    def make_operands(self) -> dict:
        self._require_one_group_a_contraction()
        self.dep = Deployment(self.config["recipe"], self.molecules(),
                              int(self.config["pattern_seed"]), self.seed,
                              dtype=self.dtype)
        self.rows = self.dep.sample_rows(self.seed)
        return dict(self.dep.describe(), checked_rows=self.rows.tolist())

    def _require_one_group_a_contraction(self) -> None:
        """On one water: the program must run each contraction of a
        batch as one TAS group and count it
        (`dbcsr_tpu_tas_groups_total`, one a group).  One that splits
        the product unasked and counts nothing compiles a hundred
        programs a batch at this size and does not end a batch in
        minutes: it fails here, in seconds, rather than hang."""
        from dbcsr_tpu.obs import metrics

        def groups():
            return sum(v for _, v in metrics.counter_items(
                "dbcsr_tpu_tas_groups_total"))

        before = groups()
        probe = Deployment(dict(self.config["recipe"], batches=1), 1,
                           int(self.config["pattern_seed"]), self.seed,
                           dtype=self.dtype)
        probe.run_batch(0)
        if groups() - before != 3:
            raise RuntimeError(
                "rpa_chi: three contractions on one water moved "
                f"dbcsr_tpu_tas_groups_total by {groups() - before}, not 3 "
                "(one TAS group each): this program splits a one-chip "
                "contraction unasked")

    def distinct_products(self) -> list:
        return list(range(self.dep.batches))

    def schedule(self):
        """Closed loop, one client: batch after batch of the tau point,
        the next when the last is done."""
        return itertools.cycle(range(self.dep.batches))

    def start(self, product: int):
        return self.dep.run_batch(product)

    @staticmethod
    def result_arrays(chi) -> list:
        return [b.data for b in chi.matrix.bins]

    @staticmethod
    def algorithm(chi):
        return getattr(chi.matrix, "_mm_algorithm", None)

    # -- the yardstick ----------------------------------------------------
    def reference(self, product: int) -> Batch:
        if product not in self._refs:
            self._refs[product] = self.dep.reference(product, rows=self.rows)
        return self._refs[product]

    def stacks(self, product: int) -> list:
        return batch_stacks(self.reference(product).infos)

    def flops(self, product: int) -> int:
        return self.bench.arithmetic.true_flops(self.stacks(product))

    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize

    def remap_bytes(self, product: int) -> int:
        """Least bytes the tensor layer's block moves of one batch take
        (`tensor_remap_s`'s modules), each moved block read and written
        once: step 3 lays out anew the blocks M^occ and M^virt keep
        (the remap's gather, then its staging scatter: twice), and each
        of the three results is mapped back into its tensor (a staging
        scatter of the blocks the product made, before the filter).
        Steps 1 and 2 take their operands in the layout they have."""
        ref = self.reference(product)
        born = sum(info["born_elements"] for info in ref.infos)
        return self.itemsize() * (2 * 2 * ref.m_elements + 2 * born)

    def check(self, product: int, chi) -> dict:
        result = self.dep.check(product, chi, rows=self.rows,
                                ref=self.reference(product))
        result["checked_rows"] = self.rows.tolist()
        return result
