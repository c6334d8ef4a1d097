"""The stack engine's device programs carry the phase scopes the
benchmark reads (`core.timings.device_scope`), under module names its
`stack_launch_s` patterns match.  The names are a contract: a scope
exists only in the lowered program's metadata, so they are read there.
"""

import fnmatch
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from dbcsr_tpu.acc import smm
from dbcsr_tpu.core.timings import device_scope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = {"stk_gather", "stk_dot", "stk_accum", "stk_loop"}


def _operands(m, n, k, dtype=jnp.float32):
    c = jnp.zeros((4, m, n), dtype)
    a = jnp.ones((6, m, k), dtype)
    b = jnp.ones((6, k, n), dtype)
    return c, a, b


def _flat_idx():
    idx = jnp.zeros((2, 3), jnp.int32)
    return idx, idx, idx


def _group_idx():
    """Live chunk count, then two width classes' (ga, gb, gc)."""
    wide = jnp.zeros((2, 3, 2), jnp.int32)
    narrow = jnp.zeros((2, 4, 1), jnp.int32)
    return (jnp.asarray(2, jnp.int32),
            wide, wide, jnp.zeros((2, 3), jnp.int32),
            narrow, narrow, jnp.zeros((2, 4), jnp.int32))


def _lower_span(fn, idx):
    c, a, b = _operands(5, 5, 3)
    return fn.lower(c, a, b, *idx, jnp.asarray(1.0, c.dtype))


def _lower_fused():
    # span 0: xla_group with both pad rows appended; span 1: xla, k = 3
    sig = ("xla", False, (("xla_group", 7, True, True, 1, False, None, "compiler"),
                          ("xla", 3, False, False, 1, False, None, "compiler")))
    c, a0, b0 = _operands(5, 5, 5)
    _, a1, b1 = _operands(5, 5, 3)
    return smm._fused_fn(sig).lower(
        c, jnp.asarray(1.0, c.dtype), a0, b0, *_group_idx(),
        a1, b1, *_flat_idx())


def _lower_fused_pallas():
    sig = ("pallas", True, (("pallas", 3, True, False, 1, False, None, "compiler"),))
    c, a, b = _operands(8, 8, 8)
    launch = (jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32),
              jnp.zeros(4, jnp.int32))
    with jax.enable_x64(False):
        return smm._fused_fn(sig).lower(
            c, jnp.asarray([[1.0]], jnp.float32), a, b, *launch)


CASES = {
    "xla": (lambda: _lower_span(smm._process_stack_xla, _flat_idx()),
            "jit__stack_phases_xla", PHASES),
    "xla_flat": (lambda: _lower_span(smm._process_stack_xla_flat,
                                     _flat_idx()),
                 "jit__stack_phases_xla_flat", PHASES),
    "xla_group": (lambda: _lower_span(smm._process_stack_xla_group,
                                      _group_idx()),
                  "jit__stack_phases_group", PHASES),
    "fused": (_lower_fused, "jit_fused_superstack",
              PHASES | {"stk_pad", "span0.xla_group.5x5x5",
                        "span1.xla.5x5x3"}),
    "fused_pallas": (_lower_fused_pallas, "jit_fused_superstack",
                     {"stk_pad", "span0.pallas.8x8x8"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_carries_its_scopes_under_a_matched_module_name(case):
    lower, module, scopes = CASES[case]
    lowered = lower()
    text = lowered.as_text(debug_info=True)
    assert re.search(r"module @(\w+)", text).group(1) == module
    with open(os.path.join(REPO, "benchmark", "layers",
                           "stack_launch_s.json")) as fh:
        patterns = json.load(fh)["modules"]
    assert any(fnmatch.fnmatchcase(module, p) for p in patterns)
    # every scope is a component of some op's name stack
    parts = {part for loc in re.findall(r'loc\("([^"]+)"', text)
             for part in loc.split("/")}
    assert scopes <= parts, scopes - parts
    # a span's phases sit inside it: the benchmark's `*/stk_dot/*`
    if case == "fused":
        names = set(re.findall(r'op_name="([^"]+)"',
                               lowered.compile().as_text()))
        assert any(fnmatch.fnmatchcase(
            n, "jit(fused_superstack)/span0.xla_group.5x5x5/stk_loop/"
            "*/stk_dot/*") for n in names), sorted(names)
        assert any(fnmatch.fnmatchcase(
            n, "jit(fused_superstack)/span1.xla.5x5x3/stk_loop/"
            "*/stk_accum/*") for n in names)
        assert any(n.startswith("jit(fused_superstack)/"
                                "span0.xla_group.5x5x5/stk_pad/")
                   for n in names)


def test_device_scope_is_a_named_scope_and_nothing_else():
    @jax.jit
    def f(x):
        with device_scope("stk_probe"):
            return x + 1

    assert "stk_probe" in f.lower(1.0).as_text(debug_info=True)
    with device_scope("outside_any_trace"):  # no tracer, timer or knob
        assert float(f(1.0)) == 2.0
