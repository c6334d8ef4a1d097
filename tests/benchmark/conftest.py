"""A rehearsal on the CPU hands the trace reductions a hand-written
trace (`fixtures/synthetic_trace.json`, in place of `xplane.load`),
because a CPU trace has no device plane.  `reducers/device_time_by_
scope.py` reads an op's scope from the trace *file*, through
`opmeta.device_ops`; a test that asks for the hand-written trace is
handed the hand-written metadata of the same ops
(`fixtures/synthetic_opmeta.json`) the same way.  It happens here,
for every test that uses `synthetic`, because the test that pins which
metrics such a rehearsal reports may not be edited by the PR that
added the scope metrics."""

import json
import os

import pytest

from benchmark import opmeta
from benchmark.fixtures.tiny import REPO


@pytest.fixture()
def synthetic_opmeta(monkeypatch):
    with open(os.path.join(REPO, "benchmark", "fixtures",
                           "synthetic_opmeta.json")) as fh:
        meta = {k: v for k, v in json.load(fh).items()
                if not k.startswith("_")}
    monkeypatch.setattr(opmeta, "device_ops", lambda path: meta)
    return meta


@pytest.fixture(autouse=True)
def _metadata_beside_the_synthetic_trace(request):
    if "synthetic" in request.fixturenames:
        request.getfixturevalue("synthetic_opmeta")
