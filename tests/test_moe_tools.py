"""The expert layer's microbenchmarks (`tools/mb_moe_rows.py`,
`tools/mb_gmm_tiles.py`) start, refuse to time off the TPU, and walk
through at a tiny size in interpret mode."""
import os
import subprocess
import sys


def _tool(*argv, name="mb_moe_rows.py"):
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name)
    return subprocess.run(
        [sys.executable, tool] + list(argv), capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_microbenchmark_of_the_row_passes_starts():
    done = _tool("--help")
    assert done.returncode == 0, done.stderr[-2000:]
    for flag in ("--walk-through", "--cell", "--rows-in-use"):
        assert flag in done.stdout, flag
    done = _tool("--cell", "kimi", "--tokens", "64")    # no TPU, no flag
    assert done.returncode == 1 and "not a TPU" in done.stderr


def test_the_microbenchmark_of_the_grouped_matmuls_tiles_walks_through():
    """Off the TPU it exits 1 without the flag; with it, at a tiny size in
    interpret mode: one line a kernel and call at the old tiles, at the
    plan's and at explicit ones (only at the call they divide)."""
    done = _tool("--cell", "kimi-vl", "--tokens", "64",
                 name="mb_gmm_tiles.py")
    assert done.returncode == 1 and "not a TPU" in done.stderr
    done = _tool("--cell", "kimi-vl", "--walk-through", "--tokens", "256",
                 "--d", "256", "--ffn", "128", "--calls", "1", "--tiles",
                 "old", "--tiles", "plan", "--tiles", "dw=256x128",
                 name="mb_gmm_tiles.py")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    assert "1536 pairs over 8 groups in a buffer of 2560 rows" in lines[0]
    assert "no device times" in lines[1]
    calls = [i for i, line in enumerate(lines) if line.startswith("call (")]
    assert [lines[i].split(":")[0] for i in calls] \
        == ["call (K 256, N 256)", "call (K 128, N 256)"]
    first = [line.split()[:4] for line in lines[calls[0] + 1:calls[1]]]
    assert first == [[who, "moe_gmm_" + kernel, "tiles", "256x256"]
                     for who in ("old", "plan")
                     for kernel in ("fwd", "dx", "dw")] \
        + [["given", "moe_gmm_dw", "tiles", "256x128"]]
    # 256 does not divide the second call's K of 128: not run there
    assert len(lines) - calls[1] - 1 == 6
    assert all("reread" in line and "roofline" in line
               for line in lines[calls[0] + 1:calls[1]])


def test_the_microbenchmark_walks_through_both_cells_forms():
    """At a tiny size off the TPU: one line a form, each "in use" form
    under the "whole" form it replaces, at the rows asked for."""
    done = _tool("--cell", "kimi", "--rows-in-use", "150", "--walk-through",
                 "--tokens", "128", "--d", "128", "--ffn", "128",
                 "--calls", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    assert "top-8 of 256 experts, 8 held" in lines[0]
    assert "150 pairs landed" in lines[0] and "bounded=1" in lines[0]
    assert "no device times" in lines[1]
    names = [line[:66].strip() for line in lines[2:]]
    for whole, in_use in (("X -> buffer: one take", "X -> buffer, in use"),
                          ("buffer -> tokens, whole",
                           "buffer -> tokens, in use"),
                          ("moe_combine's backward, whole",
                           "moe_combine's backward, in use"),
                          ("silu(gate) * up, whole", "silu(gate) * up, in use"),
                          ("its backward, whole", "its backward, in use")):
        at = [i for i, name in enumerate(names) if name.startswith(whole)]
        assert at and names[at[0] + 1].startswith(in_use), (whole, names)
    assert all(line.rstrip().endswith("GB/s") for line in lines[2:])
