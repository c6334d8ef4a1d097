"""Generator kind `sign_chain`: one client that asks for the density of
one SCF iteration again and again, X = sign(H) by the Newton-Schulz
iteration X <- 1/2 X (3I - X^2) (CP2K `&LS_SCF`, `PURIFICATION_METHOD
SIGN`).  One product of the harness is one whole chain to `tol`: two
filtered products a step, each on the result of the last.

`start` calls `dbcsr_tpu.models.sign.sign_iteration` and nothing else of
the program.  Everything else in this file is the benchmark's own and
imports nothing of `dbcsr_tpu`: the operand H (`draw_hamiltonian`, a
gapped insulator on decaying blocks, recipe in the configuration file)
and the plain reference (`reference_chain`, NumPy float64), which
applies the same semantics to the generator's own blocks: the
element-wise Gershgorin scaling, per product the candidate test in
float32 as `dbcsr_mm_cannon.F:1098-1105` states it, the block product,
the drop of C blocks under `filter_eps`, the diagonal shift, the
difference and the stopping rule.  Its surviving stacks are the flops
and bytes the harness counts; the program must return the same flops.

What this traffic does not pay: the same H every chain, so from the
second chain on each of the 2 x steps products finds its plan in the
plan cache, as it does in the late SCF iterations whose pattern has
settled, and every program is compiled in set-up.
"""

import dataclasses
import itertools
import json
import time

import numpy as np


# ------------------------------------------------------------ block matrix
@dataclasses.dataclass
class Blocks:
    """A square block-sparse matrix on the host: entries in row-major
    block order, every block zero-padded to the largest block size."""

    sizes: np.ndarray  # (nb,) block sizes, rows and columns alike
    rows: np.ndarray   # (N,) block row of each entry
    cols: np.ndarray   # (N,) block column
    data: np.ndarray   # (N, bmax, bmax) float64, zero beyond each block

    @property
    def nb(self) -> int:
        return len(self.sizes)

    @property
    def keys(self) -> np.ndarray:
        return self.rows * self.nb + self.cols

    def norms(self) -> np.ndarray:
        return np.sqrt(np.einsum("nij,nij->n", self.data, self.data))

    def by_shape(self):
        """(rows, cols, (N, bm, bn) array) per distinct block shape:
        what a bulk `put_blocks` takes."""
        bm, bn = self.sizes[self.rows], self.sizes[self.cols]
        for m, n in sorted(set(zip(bm.tolist(), bn.tolist()))):
            sel = np.nonzero((bm == m) & (bn == n))[0]
            yield self.rows[sel], self.cols[sel], self.data[sel, :m, :n]

    def dense(self) -> np.ndarray:
        off = np.concatenate([[0], np.cumsum(self.sizes)])
        out = np.zeros((off[-1], off[-1]))
        for e in range(len(self.rows)):
            r, c = self.rows[e], self.cols[e]
            out[off[r]:off[r + 1], off[c]:off[c + 1]] = \
                self.data[e, :self.sizes[r], :self.sizes[c]]
        return out


# ------------------------------------------------------------- the operand
def occupied_of(sizes, occupied_per_block, reference_block: int = 23):
    """Occupied orbitals of each molecule: `occupied_per_block` of a
    `reference_block`-function molecule, in proportion for other block
    sizes and never none (4 of 23; 3 of the ragged 18)."""
    per, ref = int(occupied_per_block), int(reference_block)
    return np.asarray([max(1, int(round(per * int(s) / ref)))
                       for s in sizes], np.int64)


def _morton_order(points: np.ndarray) -> np.ndarray:
    q = np.minimum((points * 1024).astype(np.int64), 1023)
    code = np.zeros(len(points), np.int64)
    for bit in range(10):
        for d in range(3):
            code |= ((q[:, d] >> bit) & 1) << (3 * bit + d)
    return np.argsort(code, kind="stable")


def geometry(nb: int, occupancy: float, pattern_seed: int):
    """One molecule per block row at a point of the periodic unit cube,
    rows ordered along a Morton curve.  Returns (i, j, r) of the stored
    off-diagonal pairs i < j: the nearest pairs, as many as give the
    matrix ``occupancy`` with its diagonal."""
    points = np.random.default_rng(int(pattern_seed)).random((nb, 3))
    points = points[_morton_order(points)]
    delta = points[:, None, :] - points[None, :, :]
    delta -= np.round(delta)
    dist = np.sqrt((delta ** 2).sum(axis=2))
    iu, ju = np.triu_indices(nb, k=1)
    want = int(round((occupancy * nb * nb - nb) / 2.0))
    want = min(max(want, 0), len(iu))
    nearest = np.sort(np.argsort(dist[iu, ju], kind="stable")[:want])
    return iu[nearest], ju[nearest], dist[iu, ju][nearest]


def draw_hamiltonian(sizes, occupancy: float, pattern_seed: int, seed: int,
                     *, occupied_per_block: int, coupling: float,
                     decay_length: float, virtual_width: float) -> Blocks:
    """H of a gapped insulator.  Diagonal block i = diag(-1 x occupied,
    the rest evenly from +1 to +virtual_width: a spectrum wider than its
    gap, as a molecule's is); block (i,j) of a stored pair = coupling *
    exp(-r_ij / (decay_length * nb**(-1/3))) * G_ij with G_ij Gaussian of
    unit Frobenius norm and H_ji = H_ij^T (``decay_length`` is in mean
    molecular spacings, so a smaller matrix is the same material).
    Positions from ``pattern_seed``, every value from ``seed``."""
    sizes = np.asarray(sizes, np.int64)
    nb, bmax = len(sizes), int(sizes.max())
    pi, pj, r = geometry(nb, occupancy, pattern_seed)
    values = np.random.default_rng(int(seed))
    g = values.standard_normal((len(pi), bmax, bmax))
    live = np.arange(bmax)[None, :] < sizes[:, None]            # (nb, bmax)
    g *= live[pi][:, :, None] * live[pj][:, None, :]
    g /= np.sqrt(np.einsum("nij,nij->n", g, g))[:, None, None]
    g *= (coupling * np.exp(-r / (decay_length * nb ** (-1.0 / 3.0)))
          )[:, None, None]
    occupied = occupied_of(sizes, occupied_per_block)
    diag = np.zeros((nb, bmax, bmax))
    idx = np.arange(bmax)
    # occupied at -1; the virtuals evenly from +1 up to +virtual_width
    rank = (idx[None, :] - occupied[:, None]) / np.maximum(
        1, sizes - occupied - 1)[:, None]
    diag[:, idx, idx] = np.where(idx[None, :] < occupied[:, None], -1.0,
                                 1.0 + (virtual_width - 1.0) * rank)
    diag *= live[:, :, None] * live[:, None, :]
    rows = np.concatenate([np.arange(nb), pi, pj])
    cols = np.concatenate([np.arange(nb), pj, pi])
    data = np.concatenate([diag, g, g.transpose(0, 2, 1)])
    order = np.argsort(rows * nb + cols, kind="stable")
    return Blocks(sizes, rows[order], cols[order], data[order])


def gershgorin(mat: Blocks) -> float:
    """max over element rows of the sum of |a_ij| (`dbcsr_gershgorin_norm`)."""
    sums = np.zeros((mat.nb, mat.data.shape[1]))
    np.add.at(sums, mat.rows, np.abs(mat.data).sum(axis=2))
    return float(sums.max(initial=0.0))


# ---------------------------------------------------------- one product
def candidates(a: Blocks, b: Blocks, filter_eps):
    """Every (i,k,j) with A_ik and B_kj stored, as (a_ent, b_ent, keep):
    ``keep`` is the norm test of `dbcsr_mm_cannon.F:1098-1105` in
    float32, a candidate dropped when ||A_ik||^2 ||B_kj||^2 <
    (eps / blocks in A's row i)^2."""
    b_ptr = np.searchsorted(b.rows, np.arange(b.nb + 1))
    counts = (b_ptr[a.cols + 1] - b_ptr[a.cols]).astype(np.int64)
    ends = np.cumsum(counts)
    total = int(ends[-1]) if len(ends) else 0
    a_ent = np.repeat(np.arange(len(a.rows)), counts)
    b_ent = (np.arange(total) - np.repeat(ends - counts, counts)
             + np.repeat(b_ptr[a.cols], counts))
    keep = np.ones(total, bool)
    if filter_eps is not None:
        na2 = a.norms().astype(np.float32) ** 2
        nb2 = b.norms().astype(np.float32) ** 2
        row_counts = np.bincount(a.rows, minlength=a.nb)
        row_eps = (np.float32(filter_eps)
                   / np.maximum(1, row_counts).astype(np.float32)) ** 2
        keep = na2[a_ent] * nb2[b_ent] >= row_eps[a.rows[a_ent]]
    return a_ent, b_ent, keep, ends - counts, ends


def _stacks_of(sizes, i, j, k) -> list:
    """[(m, n, k, entries, c_blocks of the (m,n) bin)] of candidates."""
    m, n, kk = sizes[i], sizes[j], sizes[k]
    nb = len(sizes)
    born = np.unique(i * nb + j)
    bm, bn = sizes[born // nb], sizes[born % nb]
    out = []
    for mm, nn, kkk in sorted(set(zip(m.tolist(), n.tolist(), kk.tolist()))):
        out.append((int(mm), int(nn), int(kkk),
                    int(((m == mm) & (n == nn) & (kk == kkk)).sum()),
                    int(((bm == mm) & (bn == nn)).sum())))
    return out


def filtered_product(a: Blocks, b: Blocks, alpha: float, filter_eps, *,
                     compute=np.float64, prune: bool = True,
                     drop: bool = True):
    """alpha * A @ B as the engine defines a filtered product: the
    candidates that pass the norm test, summed per C block, then C's
    blocks with norm under ``filter_eps`` dropped.  Returns (C, info);
    ``info["stacks"]`` are the surviving stacks."""
    nb, bmax = a.nb, a.data.shape[1]
    a_ent, b_ent, keep, starts, ends = candidates(
        a, b, filter_eps if prune else None)
    ci, cj = a.rows[a_ent[keep]], b.cols[b_ent[keep]]
    stacks = _stacks_of(a.sizes, ci, cj, a.cols[a_ent[keep]])
    born = np.unique(ci * nb + cj)
    # B's blocks with the contraction index first: the kept blocks of
    # one block row side by side are one (bmax, n*bmax) panel
    bt = np.ascontiguousarray(b.data.transpose(1, 0, 2)).astype(compute)
    ad = a.data.astype(compute)
    out = np.zeros((len(born), bmax, bmax))
    row_ptr = np.searchsorted(a.rows, np.arange(nb + 1))
    born_ptr = np.searchsorted(born // nb, np.arange(nb + 1))

    acc = np.zeros((bmax, nb, bmax))
    for i in range(nb):
        lo, hi = born_ptr[i], born_ptr[i + 1]
        if lo == hi:
            continue
        acc[:] = 0.0
        for e in range(row_ptr[i], row_ptr[i + 1]):
            s0, s1 = starts[e], ends[e]
            kept = keep[s0:s1]
            if kept.all():
                panel = bt[:, b_ent[s0]:b_ent[s0] + (s1 - s0), :]
                js = b.cols[b_ent[s0]:b_ent[s0] + (s1 - s0)]
            elif kept.any():
                fs = b_ent[s0:s1][kept]
                panel, js = bt[:, fs, :], b.cols[fs]
            else:
                continue
            width = panel.shape[1]
            acc[:, js, :] += (ad[e] @ panel.reshape(bmax, width * bmax)
                              ).reshape(bmax, width, bmax)
        out[lo:hi] = acc[:, born[lo:hi] % nb, :].transpose(1, 0, 2)
    out *= alpha
    c = Blocks(a.sizes, born // nb, born % nb, out)
    norms = c.norms()
    kept_c = (norms ** 2 >= float(filter_eps) ** 2
              if drop and filter_eps is not None else np.ones(len(born), bool))
    info = {"a_blocks": len(a.rows), "b_blocks": len(b.rows),
            "candidates": len(keep), "pruned": int((~keep).sum()),
            "c_born": len(born), "c_dropped": int((~kept_c).sum()),
            "mean_run": float(keep.sum() / max(len(born), 1)),
            "flops": sum(2 * m * n * k * e for m, n, k, e, _ in stacks),
            "stacks": stacks}
    return Blocks(a.sizes, c.rows[kept_c], c.cols[kept_c],
                  out[kept_c]), info


def add_on_diag(mat: Blocks, alpha: float) -> Blocks:
    """A + alpha*I; a missing diagonal block is created."""
    nb, bmax = mat.nb, mat.data.shape[1]
    missing = np.setdiff1d(np.arange(nb), mat.rows[mat.rows == mat.cols])
    rows = np.concatenate([mat.rows, missing])
    cols = np.concatenate([mat.cols, missing])
    data = np.concatenate([mat.data, np.zeros((len(missing), bmax, bmax))])
    order = np.argsort(rows * nb + cols, kind="stable")
    rows, cols, data = rows[order], cols[order], data[order]
    on = np.nonzero(rows == cols)[0]
    idx = np.arange(bmax)
    live = idx[None, :] < mat.sizes[rows[on]][:, None]
    data[on[:, None], idx[None, :], idx[None, :]] += alpha * live
    return Blocks(mat.sizes, rows, cols, data)


def difference_norm(x: Blocks, y: Blocks) -> float:
    """||X - Y||_F on the union of the two patterns."""
    keys = np.union1d(x.keys, y.keys)
    data = np.zeros((len(keys),) + x.data.shape[1:])
    data[np.searchsorted(keys, x.keys)] += x.data
    data[np.searchsorted(keys, y.keys)] -= y.data
    return float(np.sqrt((data ** 2).sum()))


# ------------------------------------------------------------- the chain
@dataclasses.dataclass
class Chain:
    x: Blocks
    steps: int
    history: list
    products: list   # filtered_product's info, one per product
    gershgorin: float
    seconds: float


def reference_chain(h: Blocks, *, filter_eps, tol: float, max_steps: int,
                    compute=np.float64, prune: bool = True,
                    drop: bool = True) -> Chain:
    """`sign_iteration` in plain NumPy: X0 = H / ||H||_G, then
    X <- 1/2 X (3I - X^2) with both products filtered, until
    ||X_k - X_{k-1}||_F < tol or ``max_steps``."""
    t0 = time.perf_counter()
    g = gershgorin(h)
    x = Blocks(h.sizes, h.rows, h.cols, h.data * (1.0 / g if g > 0 else 1.0))
    history, products = [], []
    for _ in range(int(max_steps)):
        x2, info = filtered_product(x, x, 1.0, filter_eps, compute=compute,
                                    prune=prune, drop=drop)
        products.append(info)
        t = add_on_diag(Blocks(x2.sizes, x2.rows, x2.cols, -x2.data), 3.0)
        x_new, info = filtered_product(x, t, 0.5, filter_eps,
                                       compute=compute, prune=prune,
                                       drop=drop)
        products.append(info)
        history.append(difference_norm(x_new, x))
        x = x_new
        if history[-1] < tol:
            break
    return Chain(x, len(history), history, products, g,
                 time.perf_counter() - t0)


def chain_stacks(products: list) -> list:
    """The chain's stacks as one product of the harness: the products'
    stacks one after another.  `arithmetic.fused_stack_bytes` reads a
    bin's C blocks from the first tuple of its (m,n), once, so every
    tuple of an (m,n) carries the sum of that bin's C blocks over the
    chain's products: the bytes are then the sum of the products'."""
    total: dict = {}
    for info in products:
        for m, n, cb in {(m, n, cb) for m, n, _, _, cb in info["stacks"]}:
            total[(m, n)] = total.get((m, n), 0) + cb
    return [(m, n, k, e, total[(m, n)])
            for info in products for m, n, k, e, _ in info["stacks"]]


# ----------------------------------------------------------- the generator
class Generator:
    def __init__(self, bench, config: dict, traffic: dict, seed: int,
                 devices: list):
        self.bench = bench
        self.config, self.traffic = config, traffic
        self.seed, self.devices = seed, devices
        self.dtype = traffic["dtype"]
        self.filter_eps = float(config["filter_eps"])
        if [int(g) for g in config["grid"]] != [1, 1]:
            raise ValueError("sign_chain runs the one-chip engine: grid 1,1")
        if not (config["m"] == config["n"] == config["k"]
                and config["blocks"]["m"] == config["blocks"]["n"]
                == config["blocks"]["k"]):
            raise ValueError("sign_chain needs a square matrix, one blocking")
        assumed = config["assumed"]
        self.recipe = {key: assumed[key]["value"] for key in
                       ("occupied_per_block", "coupling", "decay_length",
                        "virtual_width")}
        self.tol = float(assumed["tol"]["value"])
        self.max_steps = int(assumed["max_steps"]["value"])
        self._chain = None
        self._history = None

    # -- set-up -----------------------------------------------------------
    def make_operands(self) -> dict:
        """Draw H from the seed and stage it; returns a description for
        the log.  The gap is centred at 0, so mu = 0 and H goes in as it
        is."""
        import dbcsr_tpu as dt

        cfg = self.config
        sizes = self.bench.arithmetic.expand_block_sizes(
            int(cfg["m"]), cfg["blocks"]["m"])
        self.h = draw_hamiltonian(
            sizes, float(cfg["occupancy"]["a"]), int(cfg["pattern_seed"]),
            self.seed, **self.recipe)
        self.occupied = int(occupied_of(
            sizes, self.recipe["occupied_per_block"]).sum())
        mat = dt.create("H", sizes.astype(np.int32), sizes.astype(np.int32),
                        self.dtype)
        for rows, cols, data in self.h.by_shape():
            mat.put_blocks(rows, cols, data.astype(self.dtype))
        self.mat_h = mat.finalize()
        norms = self.h.norms()
        off = self.h.rows != self.h.cols
        g = gershgorin(self.h)
        return {"h_blocks": len(self.h.rows), "block_rows": len(sizes),
                "dtype": self.dtype, "filter_eps": self.filter_eps,
                "tol": self.tol, "max_steps": self.max_steps,
                "occupied": self.occupied, "gershgorin": g,
                "x0_weakest_block": float(norms[off].min() / g)
                if off.any() else None,
                "x0_strongest_offdiagonal": float(norms[off].max() / g)
                if off.any() else None}

    # -- the products -----------------------------------------------------
    def distinct_products(self) -> list:
        return [0]

    def schedule(self):
        """Closed loop, one client: the next chain when the last is
        done."""
        return itertools.repeat(0)

    def start(self, product: int):
        """One chain; returns (X, flops as the program counts them)."""
        from dbcsr_tpu.models.sign import sign_iteration

        x, self._history = sign_iteration(
            self.mat_h, steps=self.max_steps, filter_eps=self.filter_eps,
            tol=self.tol)
        return x, int(getattr(x, "_last_flops", 0))

    @staticmethod
    def result_arrays(x) -> list:
        return [b.data for b in x.bins]

    @staticmethod
    def algorithm(x):
        return getattr(x, "_mm_algorithm", None)

    # -- the yardstick ----------------------------------------------------
    def chain(self) -> Chain:
        """The reference chain on the generator's own H, computed once."""
        if self._chain is None:
            self._chain = reference_chain(
                self.h, filter_eps=self.filter_eps, tol=self.tol,
                max_steps=self.max_steps)
            ch = self._chain
            print("BENCH reference_chain " + json.dumps(
                {"seconds": ch.seconds, "steps": ch.steps,
                 "history": ch.history, "x_blocks": len(ch.x.rows)}),
                flush=True)
            for n, info in enumerate(ch.products):
                print("BENCH chain " + json.dumps(
                    {k: v for k, v in dict(info, product=n).items()
                     if k != "stacks"}), flush=True)
        return self._chain

    def stacks(self, product: int) -> list:
        return chain_stacks(self.chain().products)

    def flops(self, product: int) -> int:
        return self.bench.arithmetic.true_flops(self.stacks(product))

    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize

    def tolerance(self, chain: Chain) -> float:
        """Largest elementwise error of X against X_ref, relative to
        max|X_ref|: the bound of one product (a k-deep dot summed over
        the block columns) times the products of the chain, because
        each product's rounding enters the next as data and
        Newton-Schulz does not amplify it (it contracts towards
        sign(H)).  6.4e-13 for the 14 products at the full size
        (measured 3.4e-14 on a v5e, PR 32); the chain computed in
        float32 reads 5.8e-8 there, five orders over, takes a step
        more and misses the trace by 7e-5 (full size on the CPU, seed
        2147483659, PR 32), and one that skips the filter or the
        candidate test fails the pattern, the flops and, by the 1e-9
        to 1e-7 a dropped block carries, this."""
        sizes = self.h.sizes
        return len(chain.products) * self.bench.arithmetic.reference_tolerance(
            self.dtype, int(sizes.max()), len(sizes))

    def check(self, product: int, x) -> dict:
        """The program's X against the reference chain's: steps, the
        pattern, sampled block rows, the trace.  (The flops are the
        harness's to compare.)"""
        ref = self.chain()
        steps = len(self._history)
        rows, cols = x.entry_coords()
        got_keys = np.sort(np.asarray(rows, np.int64) * self.h.nb
                           + np.asarray(cols, np.int64))
        same_pattern = np.array_equal(got_keys, ref.x.keys)
        block_rows = self.bench.reference.sample_block_rows(
            self.h.sizes, self.seed)
        sizes = self.h.sizes
        err, finite = 0.0, True
        sampled = np.isin(ref.x.rows, block_rows)
        on_diag = ref.x.rows == ref.x.cols
        ents = np.nonzero(sampled | on_diag)[0]
        # one fetch for the sampled rows and the diagonal: a call
        # compiles its gathers
        blocks = x.get_blocks(ref.x.rows[ents], ref.x.cols[ents])
        trace = 0.0  # of the fetched diagonal blocks, summed here
        for e, blk in zip(ents, blocks):
            if blk is None or not np.all(np.isfinite(blk)):
                finite = False
                continue
            blk = np.asarray(blk, np.float64)
            if on_diag[e]:
                trace += float(np.trace(blk))
            if sampled[e]:
                want = ref.x.data[e, :sizes[ref.x.rows[e]],
                                  :sizes[ref.x.cols[e]]]
                err = max(err, float(np.max(np.abs(blk - want))))
        scale = max(1.0, float(np.max(np.abs(ref.x.data))))
        tol = self.tolerance(ref)
        trace_want = float(sizes.sum() - 2 * self.occupied)
        result = {
            "steps": steps, "steps_reference": ref.steps,
            "history": [float(v) for v in self._history],
            "same_pattern": bool(same_pattern),
            "x_blocks": int(len(got_keys)),
            "x_blocks_reference": int(len(ref.x.keys)),
            "x_occupancy": len(ref.x.keys) / float(self.h.nb) ** 2,
            "rel_err": err / scale, "tol": tol, "rows": block_rows,
            "trace": trace, "trace_want": trace_want, "trace_tol": 1e-6,
            "products": len(ref.products),
        }
        result["ok"] = bool(
            steps == ref.steps and same_pattern and finite
            and err / scale <= tol and abs(trace - trace_want) <= 1e-6)
        result["first_difference"] = self._first_difference(ref)
        return result

    @staticmethod
    def _first_difference(ref: Chain):
        """The first product of the chain whose flops or surviving C
        blocks differ from the reference's, read from the program's
        flight records of the chain just run (one a product): both
        sides apply one float32 rule to norms that agree to 1e-14, so
        a differing decision is a finding and this names where."""
        from dbcsr_tpu.obs import flight

        records = flight.records()[-len(ref.products):]
        if len(records) != len(ref.products):
            return None
        for n, (rec, info) in enumerate(zip(records, ref.products)):
            want = {"flops": info["flops"],
                    "kept_blocks": info["c_born"] - info["c_dropped"]}
            got = {key: rec.get(key) for key in want}
            if got != want:
                return {"product": n, "program": got, "reference": want}
        return None
