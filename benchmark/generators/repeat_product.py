"""Generator kind `repeat_product`: one client that sends the same
product C = alpha * A @ B again and again, into a fresh C each time.

A and B are drawn once (pattern from the configuration's
`pattern_seed`, values from `--seed`: `reference.draw_blocks`), staged
through the program's public API, and stay resident; the grid of the
configuration decides between `dt.multiply` and
`parallel.sparse_multiply_distributed`.

What this traffic does not pay: A and B are the same objects every
product, so whatever the program caches by operand identity hits.  On
the dense route that is the scatter of A's and B's blocks into their
canvases, which an SCF step with new values pays every product.  A
re-valued mix (two value sets of one pattern, taken in turn) needs a
generator of its own and is an open row in PERF.md.

The traffic file gives `dtype` and `filter` (true: the configuration's
`filter_eps`); sizes, block multisets, occupancy and grid are the
configuration's.
"""

from __future__ import annotations

import itertools

import numpy as np


class Generator:
    def __init__(self, bench, config: dict, traffic: dict, seed: int,
                 devices: list):
        self.bench = bench  # the benchmark's own modules (arithmetic, reference)
        self.config, self.traffic = config, traffic
        self.seed, self.devices = seed, devices
        self.dtype = traffic["dtype"]
        self.filter_eps = (float(config["filter_eps"])
                           if traffic.get("filter") else None)
        self.alpha, self.beta = float(config["alpha"]), float(config["beta"])
        if self.beta != 0.0 or list(config["trans"]) != ["N", "N"]:
            raise ValueError("repeat_product draws an empty C and plain "
                             "A, B: beta 0 and trans N,N only")
        self.grid = [int(g) for g in config["grid"]]
        self.mesh = None
        self._stacks = None

    # -- set-up -----------------------------------------------------------
    def make_operands(self) -> dict:
        """Draw A and B from the seed and stage them; returns a
        description for the log."""
        ar, ref = self.bench.arithmetic, self.bench.reference
        cfg = self.config
        sizes = {d: ar.expand_block_sizes(int(cfg[d]), cfg["blocks"][d])
                 for d in ("m", "n", "k")}
        # the pattern belongs to the deployment (a geometry fixes it),
        # the values to the run: every seed then runs the same shapes,
        # the same flops and the same compiled programs
        pattern = np.random.default_rng(int(cfg["pattern_seed"]))
        values = np.random.default_rng(self.seed)
        self.a = ref.draw_blocks(pattern, values, sizes["m"], sizes["k"],
                                 float(cfg["occupancy"]["a"]), self.dtype)
        self.b = ref.draw_blocks(pattern, values, sizes["k"], sizes["n"],
                                 float(cfg["occupancy"]["b"]), self.dtype)
        self.mat_a = self._stage("A", self.a)
        self.mat_b = self._stage("B", self.b)
        if self.grid != [1, 1]:
            from dbcsr_tpu.parallel import make_grid

            want = self.grid[0] * self.grid[1]
            self.mesh = make_grid(want, devices=self.devices[:want])
            shape = dict(self.mesh.shape)
            if [shape["pr"], shape["pc"]] != self.grid:
                raise ValueError(f"grid {self.grid} wanted, mesh {shape}")
        return {"a_blocks": self.a.nblks, "b_blocks": self.b.nblks,
                "block_rows": len(sizes["m"]), "dtype": self.dtype,
                "filter_eps": self.filter_eps, "grid": self.grid}

    def _stage(self, name: str, blocks):
        import dbcsr_tpu as dt

        m = dt.create(name, blocks.row_sizes.astype(np.int32),
                      blocks.col_sizes.astype(np.int32), self.dtype)
        for rows, cols, data in blocks.by_shape():
            m.put_blocks(rows, cols, data)
        return m.finalize()

    # -- the products -----------------------------------------------------
    def distinct_products(self) -> list:
        return [0]

    def schedule(self):
        """Closed loop, one client: the next product when the last is
        done, for as long as the harness asks."""
        return itertools.repeat(0)

    def start(self, product: int):
        """Call the program; returns (C, flops as the program counts
        them) as soon as the program returns."""
        import dbcsr_tpu as dt

        if self.mesh is None:
            c = dt.create("C", self.mat_a.row_blk_sizes,
                          self.mat_b.col_blk_sizes, self.dtype)
            flops = dt.multiply("N", "N", self.alpha, self.mat_a, self.mat_b,
                                self.beta, c, filter_eps=self.filter_eps)
            return c, int(flops)
        from dbcsr_tpu.parallel import sparse_multiply_distributed

        c = sparse_multiply_distributed(
            self.alpha, self.mat_a, self.mat_b, self.beta, None, self.mesh,
            filter_eps=self.filter_eps)
        return c, int(getattr(c, "_last_flops", 0))

    @staticmethod
    def result_arrays(c) -> list:
        """Every device array the product is made of."""
        return [b.data for b in c.bins]

    @staticmethod
    def algorithm(c):
        return getattr(c, "_mm_algorithm", None)

    # -- the yardstick ----------------------------------------------------
    def stacks(self, product: int) -> list:
        if self._stacks is None:
            a, b = self.a, self.b
            self._stacks = self.bench.arithmetic.product_stacks(
                a.rows, a.cols, b.rows, b.cols,
                a.row_sizes, a.col_sizes, b.col_sizes)
        return self._stacks

    def flops(self, product: int) -> int:
        return self.bench.arithmetic.true_flops(self.stacks(product))

    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize

    def check(self, product: int, c) -> dict:
        """C against the NumPy float64 product of the generator's own
        blocks, on sampled block rows."""
        ar, ref = self.bench.arithmetic, self.bench.reference
        block_rows = ref.sample_block_rows(self.a.row_sizes, self.seed)
        want = ref.product_rows(self.a, self.b, block_rows, self.alpha)
        nbc = len(self.b.col_sizes)
        col_off = np.concatenate([[0], np.cumsum(self.b.col_sizes)])
        got = {}
        for r in block_rows:
            panel = np.zeros(want[r].shape, np.float64)
            blocks = c.get_blocks(np.full(nbc, r), np.arange(nbc))
            for j, blk in enumerate(blocks):
                if blk is not None:
                    panel[:, col_off[j]:col_off[j + 1]] = blk
            got[r] = panel
        tol = ar.reference_tolerance(
            self.dtype, int(self.a.col_sizes.max()), len(self.a.col_sizes))
        result = ref.compare_rows(want, got, tol)
        result["row_block_sizes"] = sorted(
            {int(self.a.row_sizes[r]) for r in block_rows})
        return result
