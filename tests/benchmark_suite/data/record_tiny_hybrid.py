"""How data/tiny_hybrid_tpu.xplane.pb.gz was recorded (PR 26), to record it
again after a change to what the program writes into a trace:

    chiprun --chips 1 -- python3 tests/benchmark_suite/data/record_tiny_hybrid.py
    cp chiprun_out/tiny_hybrid_tpu.xplane.pb.gz tests/benchmark_suite/data/

Six layers of `models/phi4flash.py`, one of each kind, at hidden 256, 4/2 heads of 64,
E=512, N=16, window 128, seq 512, batch 1, bf16, recompute on: the two
selective-scan kernels and the flash kernels in their window, full and cross
modes, driven 3 steps through `Executor.run` with obs on, inside
`bench.traced` / `bench.exe_run` as the harness has them. The
`/host:metadata` plane is left out and the file gzipped, as
`record_tiny_exec.py` does. Needs a TPU; not a test.
"""
import glob
import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
sys.path.insert(0, HERE)


def main():
    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.framework import obs
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import phi4flash
    from record_tiny_exec import without_plane

    cfg = phi4flash.Phi4FlashConfig(
        vocab_size=1024, hidden_size=256, num_heads=4, num_kv_heads=2,
        head_dim=64, ff_size=512, ssm_inner=512, ssm_state=16,
        ssm_dt_rank=16, window=128,
        layer_kinds=["mamba", "window", "memory", "full", "gmu", "cross"],
        published_layer_index=[0, 1, 16, 17, 18, 19], dtype="bfloat16",
        recompute=True)
    main_prog, startup, _feeds, fetch = phi4flash.phi4flash_pretrain_program(
        cfg, 1, 512,
        optimizer_fn=lambda loss: optimizer.Adam(1e-4).minimize(loss))
    toks = np.random.RandomState(0).randint(0, 1024, (1, 513)).astype(
        np.int64)
    feed = {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((1, 512, 1), np.float32)}
    scope = Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    exe.run(startup, scope=scope)

    def step():
        return exe.run(main_prog, feed=feed, fetch_list=[fetch["loss"]],
                       scope=scope)

    for _ in range(3):
        print("loss", float(step()[0].reshape(-1)[0]))
    where = os.path.join("chiprun_out", "tiny_hybrid_trace")
    obs.clear()
    obs.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.exe_run"):
                step()
    jax.profiler.stop_trace()
    obs.disable()
    found = sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                             recursive=True))[-1]
    with open(found, "rb") as f:
        small = without_plane(f.read(), "/host:metadata")
    out = os.path.join("chiprun_out", "tiny_hybrid_tpu.xplane.pb.gz")
    with open(out, "wb") as f:
        f.write(gzip.compress(small, 9, mtime=0))
    print("%s: %d bytes (%d before gzip)" % (out, os.path.getsize(out),
                                             len(small)))


if __name__ == "__main__":
    main()
