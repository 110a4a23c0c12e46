"""The benchmark's tests: CPU, tier-1. The repo root goes on sys.path so
`benchmark` (the package beside `paddle_tpu`) imports."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A temp copy of the benchmark with the tiny cells added."""
    import tiny_root
    return tiny_root.make(tmp_path_factory.mktemp("bench"))
