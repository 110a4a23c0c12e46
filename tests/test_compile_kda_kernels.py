"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
`kimi-linear-48b-a3b.t8192-b2` step program holds the delta rule as its
Pallas kernels (`kda_fwd` eight times: four layers, forward and recompute's
replay; `kda_bwd` four times), no loop is left under a `kda_attention`
scope in either role (the XLA form's scans over groups and chunks are gone:
the chunk axis is the kernels' grid), and it fits the chip. The topology is
described inside `tests/benchmark_suite/test_compile_fullsize.py`'s fixture,
which skips where it cannot be."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "benchmark_suite")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import (device_bytes, lower_step,  # noqa: E402
                                   no_compile_cache, topo)    # noqa: F401

CHIP_BYTES = 16909336064        # bytes_limit a v5e reports: 15.75 GiB
CELL = "kimi-linear-48b-a3b.t8192-b2"
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(text, opcode):
    """The `op_name` of every instruction of `opcode` in the HLO text."""
    found = []
    for line in text.splitlines():
        if " %s(" % opcode in line:
            name = OP_NAME.search(line)
            found.append(name.group(1) if name else "")
    return found


@pytest.mark.slow
def test_the_kimi_step_holds_the_delta_rule_as_kernels_and_no_loop_of_it(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    """Behind `slow`: `tests/benchmark_suite/test_compile_kimilinear.py::
    test_step_compiles_for_v5e_fits_and_holds_no_history` compiles the same
    step; its re-pin (ROADMAP C1 (j)) brings this guard back into tier-1."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert need < CHIP_BYTES
    text = compiled.as_text()
    calls = [n for n in _op_names(text, "custom-call") if "/kda_" in n]
    kernels = [re.search(r"/(kda_[a-z_]+)/pallas_call", n).group(1)
               for n in calls]
    assert kernels.count("kda_fwd") == 8, kernels
    assert kernels.count("kda_bwd") == 4, kernels
    assert set(kernels) == {"kda_fwd", "kda_bwd"}
    # every call lies under the op's scope, where `kda_device_ms` reads it
    assert all("/kda_attention" in n for n in calls), calls
    forward = [n for n in calls if n.startswith("jit(step)/forward/")]
    assert len(forward) == 4 and all("kda_fwd" in n for n in forward)
    # the step has loops (the expert layer's, the head's), none the delta
    # rule's: none is left on purpose
    loops = _op_names(text, "while")
    assert loops
    assert not [n for n in loops if "kda_attention" in n], loops
