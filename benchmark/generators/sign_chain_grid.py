"""Generator kind `sign_chain_grid`: the traffic of `sign_chain` on a
process grid.  One client asks for the density of one SCF iteration
again and again; one product of the harness is one whole Newton-Schulz
sign chain to `tol`, and with a grid in the configuration every
product of the chain runs on the mesh of `parallel.make_grid`, through
`dbcsr_tpu.models.sign.sign_iteration(..., mesh=mesh)`.

Everything but the mesh is `sign_chain.py`'s, loaded from the file
beside this one and not copied: the operand H and its recipe, the
plain reference (`reference_chain`, NumPy float64, nothing of
`dbcsr_tpu`), the stacks, the flops, the tolerance and `check`.  The
layout changes no answer: the same H gives the same X, the same
pattern and the same flops on any grid.
"""

import importlib.util
import os


def _load_beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "_bench_generators_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sign_chain = _load_beside("sign_chain")


class Generator(sign_chain.Generator):
    def __init__(self, bench, config: dict, traffic: dict, seed: int,
                 devices: list):
        # `sign_chain.Generator` runs the one-chip engine and refuses a
        # grid: it is given the configuration without one, and the grid
        # is kept here
        super().__init__(bench, dict(config, grid=[1, 1]), traffic, seed,
                         devices)
        self.config = config
        self.grid = [int(g) for g in config["grid"]]
        self.mesh = None

    def make_operands(self) -> dict:
        """H as `sign_chain` draws and stages it, and the mesh."""
        from dbcsr_tpu.parallel import make_grid

        described = super().make_operands()
        want = self.grid[0] * self.grid[1]
        self.mesh = make_grid(want, devices=self.devices[:want])
        shape = dict(self.mesh.shape)
        if [shape["pr"], shape["pc"]] != self.grid:
            raise ValueError(f"grid {self.grid} wanted, mesh {shape}")
        return dict(described, grid=self.grid)

    def start(self, product: int):
        """One chain on the grid; returns (X, flops as the program
        counts them)."""
        from dbcsr_tpu.models.sign import sign_iteration

        x, self._history = sign_iteration(
            self.mat_h, steps=self.max_steps, filter_eps=self.filter_eps,
            tol=self.tol, mesh=self.mesh)
        return x, int(getattr(x, "_last_flops", 0))
