"""tools/dot_inventory.py: the dot_general inventory parser."""
import numpy as np


SNIPPET = """
  %54 = stablehlo.dot_general %53, %arg45, contracting_dims = [1] x [0],
    precision = [DEFAULT, DEFAULT] :
    (tensor<512x256xbf16>, tensor<256x1024xbf16>) -> tensor<512x1024xbf16>
  %60 = stablehlo.dot_general %59, %arg46, batching_dims = [0] x [0],
    contracting_dims = [2] x [1], precision = [HIGHEST, HIGHEST] :
    (tensor<8x64x32xf32>, tensor<8x32x16xf32>) -> tensor<8x64x16xf32>
"""


def test_dot_inventory_parses_stablehlo(capsys):
    from tools.dot_inventory import dot_inventory
    dots = dot_inventory(SNIPPET, top_k=5)
    assert len(dots) == 2
    by_out = {d["out"]: d for d in dots}
    d1 = by_out["512x1024xbf16"]
    assert d1["bf16_operands"] and d1["precision"] == "DEFAULT"
    # 2 * 512*1024 * 256 = 268.4 MF
    np.testing.assert_allclose(d1["gflops"],
                               round(2 * 512 * 1024 * 256 / 1e9, 3))
    d2 = by_out["8x64x16xf32"]
    assert not d2["bf16_operands"] and d2["precision"] == "HIGHEST"
    # contraction dim 2 of lhs = 32: 2 * (8*64*16) * 32
    np.testing.assert_allclose(d2["gflops"],
                               round(2 * 8 * 64 * 16 * 32 / 1e9, 3))
    out = capsys.readouterr().out
    assert "NOT bf16" in out and "precision=HIGHEST" in out
