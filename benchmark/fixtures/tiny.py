"""A checkout in miniature: a copy of `benchmark/` and `BENCHMARK.json`
whose configurations are cut to a few blocks, for rehearsals on the CPU
(the tests) and for recording the small trace kept beside this file.

    python benchmark/fixtures/tiny.py <cell> <out.xplane.pb>

records that trace on a TPU: the cell's traced window at the tiny size.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(_HERE))


def tiny_checkout(dst: str, *, uniform: int = 43, mixed: int = 120,
                  occupancy: float = 0.4) -> str:
    """Copy the benchmark into ``dst`` with every configuration cut to
    ``uniform`` (blocks of 5 and a ragged 3, like 434 x 23 + 18) or, with
    several block sizes, ``mixed`` rows."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    for entry in spec["configs"]:
        path = os.path.join(dst, entry["file"])
        with open(path) as fh:
            cfg = json.load(fh)
        if all(len(cfg["blocks"][d]) == 1 for d in "mnk"):
            cfg.update(m=uniform, n=uniform, k=uniform,
                       blocks={d: [[1, 5]] for d in "mnk"})
        else:
            cfg.update(m=mixed, n=mixed, k=mixed)
        cfg["occupancy"] = {"a": occupancy, "b": occupancy}
        with open(path, "w") as fh:
            json.dump(cfg, fh)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return dst


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    from benchmark import harness, xplane

    cell, out = sys.argv[1], sys.argv[2]
    dst = os.path.join(REPO, harness.OUT_DIR, "tiny")
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst)
    tiny_checkout(dst)
    rc = harness.run_cell(dst, cell, 3, 2.0, True)
    if rc == 0:
        shutil.copy(xplane.find_xplane(os.path.join(
            dst, harness.OUT_DIR, "trace", cell)), out)
    sys.exit(rc)
