"""How data/tiny_exec_tpu.xplane.pb.gz was recorded (PR 24), to record it
again after a change to what the program writes into a trace:

    chiprun --chips 1 -- python3 tests/benchmark_suite/data/record_tiny_exec.py
    cp chiprun_out/tiny_exec_tpu.xplane.pb.gz tests/benchmark_suite/data/

A 2-layer GPT-style program (hidden 128, 2 heads, seq 512, batch 2, bf16,
recompute on, the Pallas flash kernels) driven 3 steps through
`Executor.run` with obs on, inside `bench.traced` / `bench.exe_run` as the
harness has them. The `/host:metadata` plane (the module's HLO proto, 1.1 of
the file's 1.6 MB, read by nothing here) is left out and the file gzipped:
84 KB. Needs a TPU; not a test.
"""
import glob
import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def without_plane(space, name):
    """The serialized XSpace `space` minus the planes called `name`."""
    from benchmark.layer_metrics import _scopes
    out = bytearray()
    for num, wire, value in _scopes._fields(memoryview(space)):
        if wire != 2:
            raise ValueError("an XSpace holds only messages and strings")
        if num == 1 and any(n == 2 and _scopes._text(v) == name
                            for n, _w, v in _scopes._fields(value)):
            continue
        out += _varint((num << 3) | 2) + _varint(len(value)) + bytes(value)
    return bytes(out)


def main():
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.framework import obs
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=2, ff_size=512, max_position=512,
                        dropout=0.0, dtype="bfloat16", attn_impl="flash",
                        recompute=True)
    main_prog, startup, _feeds, fetch = gpt.gpt_pretrain_program(
        cfg, 2, 512,
        optimizer_fn=lambda loss: optimizer.Adam(1e-4).minimize(loss))
    feed = gpt.synthetic_batch(cfg, 2, 512)
    scope = Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    exe.run(startup, scope=scope)

    def step():
        exe.run(main_prog, feed=feed, fetch_list=[fetch["loss"]],
                scope=scope)

    for _ in range(3):
        step()
    where = os.path.join("chiprun_out", "tiny_exec_trace")
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
    out = os.path.join("chiprun_out", "tiny_exec_tpu.xplane.pb.gz")
    with open(out, "wb") as f:
        f.write(gzip.compress(small, 9, mtime=0))
    print("%s: %d bytes (%d before gzip)" % (out, os.path.getsize(out),
                                             len(small)))


if __name__ == "__main__":
    main()
