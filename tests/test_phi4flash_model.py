"""models/phi4flash.py: the layout rule, the config's checks, and the
program trained through Executor as one jitted step."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.models import phi4flash as pm

CUT = ["mamba", "window", "memory", "full", "gmu", "cross"]


def tiny(**kw):
    base = dict(vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
                head_dim=16, ff_size=128, ssm_inner=128, ssm_state=4,
                ssm_dt_rank=4, window=8, layer_kinds=CUT,
                published_layer_index=[0, 1, 16, 17, 18, 19],
                recompute=True)
    base.update(kw)
    return pm.Phi4FlashConfig(**base)


def test_the_published_layout_rule():
    kinds, index = pm.layout(8)
    assert kinds == ["mamba", "window", "mamba", "window", "memory", "full",
                     "gmu", "cross"]
    assert index == list(range(8))
    kinds, _ = pm.layout(32)
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["memory", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    with pytest.raises(ValueError, match="% 4"):
        pm.layout(6)
    # the defaults are the published model's
    cfg = pm.Phi4FlashConfig()
    assert (cfg.num_layers, cfg.ssm_inner, cfg.ssm_dt_rank, cfg.window) \
        == (32, 5120, 160, 512)
    assert pm.lambda_init(0) == pytest.approx(0.2)
    assert pm.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


@pytest.mark.parametrize("kw,match", [
    (dict(layer_kinds=["gmu", "memory"], published_layer_index=[0, 1]),
     "gmu before"),
    (dict(layer_kinds=["cross"], published_layer_index=[0]), "cross"),
    (dict(layer_kinds=["mamba", "conv"], published_layer_index=[0, 1]),
     "unknown"),
    (dict(num_heads=6, num_kv_heads=4), "pair"),
    (dict(published_layer_index=[0, 1]), "one entry a layer"),
])
def test_a_layout_that_cannot_run_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


@pytest.mark.parametrize("dtype,recompute", [("float32", True),
                                             ("bfloat16", True),
                                             ("float32", False)])
def test_the_program_trains_as_one_jitted_step(dtype, recompute):
    cfg = tiny(dtype=dtype, recompute=recompute)
    main, startup, feeds, fetch = pm.phi4flash_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(2e-3).minimize)
    assert feeds == ["token_ids", "labels", "loss_mask"]
    types = [op.type for op in main.global_block().ops]
    assert (types.count("remat_block") == 6) == recompute
    exe = pt.Executor()
    exe.run(startup)
    toks = np.random.RandomState(0).randint(0, 96, (2, 33)).astype(np.int64)
    feed = {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, 32, 1), np.float32)}
    misses = exe.cache_misses
    losses = [float(exe.run(main, feed=feed,
                            fetch_list=[fetch["loss"]])[0].reshape(-1)[0])
              for _ in range(25)]
    assert exe.cache_misses == misses + 1       # one compiled step
    assert losses[0] == pytest.approx(np.log(96), rel=0.05)
    assert losses[-1] < 0.75 * losses[0]
    # the model's own initialisation: Alog = log(1..N), D = 1
    scope = pt.global_scope()
    np.testing.assert_allclose(
        np.exp(np.asarray(scope.find_var("phi_layer_0_A_log")))[0],
        np.arange(1, 5) * np.exp(np.asarray(scope.find_var(
            "phi_layer_0_A_log"))[0, 0]), rtol=0.2)
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"phi_layer_2_A_log", "phi_layer_3_qkv.w_0",
            "phi_layer_4_gmu_in.w_0", "phi_layer_5_q.w_0",
            "phi_layer_5_subln_s", "phi_lnf_s"} <= names
    # cross attention has no key/value projection of its own
    assert "phi_layer_5_qkv.w_0" not in names
