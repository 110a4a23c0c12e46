"""Block-quantization codec property tests (ops/quant_ops + the
quantized collective kernels in ops/collective_ops).

The codec underwrites three production paths — quantized gradient
all-reduce, elastic state shipping, compressed checkpoints — so its
error envelope, poison semantics and byte accounting are pinned here
property-style, not assumed."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import quant_ops as qo
from paddle_tpu.ops import collective_ops as co

pytestmark = pytest.mark.quant


# ---------------------------------------------------------------------------
# round-trip error bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dtype", [
    ((300,), np.float32), ((64, 5), np.float32), ((1000,), np.float64),
    ((7,), np.float32), ((256,), np.float32), ((2, 3, 50), np.float32),
])
def test_np_codec_roundtrip_error_bound_per_block(shape, dtype):
    """Every element is within absmax_block/(2*qmax) of its value — the
    per-block abs-max quantization bound — and the max-magnitude element
    of every block round-trips exactly."""
    rng = np.random.RandomState(hash(shape) % 2**31)
    x = (rng.randn(*shape) *
         10.0 ** rng.randint(-3, 4, shape)).astype(dtype)
    block = 64
    q, scale = qo.np_block_quantize(x, block_size=block)
    back = qo.np_block_dequantize(q, scale, x.shape, x.dtype, bits=8)
    flat = np.asarray(x, np.float32).reshape(-1)
    pad = (-flat.size) % block
    padded = np.concatenate([flat, np.zeros(pad, np.float32)])
    blocks = padded.reshape(-1, block)
    bound = np.abs(blocks).max(axis=1) / 127.0 * 0.5
    err = np.abs(np.asarray(back, np.float32).reshape(-1) - flat)
    err_blocks = np.concatenate(
        [err, np.zeros(pad, np.float32)]).reshape(-1, block)
    # float64 inputs quantize through fp32 scales: allow fp32 ulp slack
    slack = 1e-6 * np.abs(blocks).max(axis=1) + 1e-12
    assert (err_blocks.max(axis=1) <= bound + slack).all()
    # the abs-max element of each block is exact (q = ±qmax exactly)
    amax_idx = np.abs(blocks).argmax(axis=1)
    deq_blocks = np.concatenate(
        [np.asarray(back, np.float32).reshape(-1),
         np.zeros(pad, np.float32)]).reshape(-1, block)
    for b in range(blocks.shape[0]):
        np.testing.assert_allclose(deq_blocks[b, amax_idx[b]],
                                   blocks[b, amax_idx[b]], rtol=1e-6)


def test_jnp_and_np_codec_agree():
    rng = np.random.RandomState(0)
    x = rng.randn(500).astype(np.float32)
    qn, sn = qo.np_block_quantize(x, block_size=128)
    qj, sj = qo.block_quantize(jnp.asarray(x), block_size=128)
    np.testing.assert_array_equal(qn, np.asarray(qj))
    np.testing.assert_allclose(sn, np.asarray(sj), rtol=1e-7)
    back_j = qo.block_dequantize(qj, sj, x.shape, jnp.float32)
    back_n = qo.np_block_dequantize(qn, sn, x.shape, np.float32)
    np.testing.assert_allclose(np.asarray(back_j), back_n, rtol=1e-6)


def test_all_zero_block_roundtrips_to_zero():
    x = np.zeros(300, np.float32)
    q, s = qo.np_block_quantize(x, block_size=128)
    back = qo.np_block_dequantize(q, s, x.shape, x.dtype)
    np.testing.assert_array_equal(back, x)


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_nonfinite_input_poisons_its_block_only(bad):
    """A NaN/Inf element must NOT be silently clipped to a finite value:
    its whole block dequantizes to NaN (check_numerics catches it), and
    OTHER blocks stay healthy."""
    x = np.ones(256, np.float32)
    x[3] = bad
    q, s = qo.np_block_quantize(x, block_size=128)
    back = qo.np_block_dequantize(q, s, x.shape, x.dtype)
    assert not np.isfinite(back[:128]).any()
    np.testing.assert_allclose(back[128:], x[128:], rtol=1e-2)
    # jnp half agrees on the poison semantics
    bj = qo.block_dequantize(*qo.block_quantize(jnp.asarray(x), 128),
                             shape=x.shape, dtype=jnp.float32)
    bj = np.asarray(bj)
    assert not np.isfinite(bj[:128]).any()
    assert np.isfinite(bj[128:]).all()


def test_quantized_wire_bytes_math():
    # 1000 fp32 values, block 256 -> 4 blocks: 1024 int8 + 4*4B scales
    raw, wire = qo.quantized_wire_bytes(1000, 4, block_size=256, bits=8)
    assert raw == 4000 and wire == 1024 + 16
    assert qo.quantized_wire_bytes(0, 4) == (0, 0)
    # the headline ratio: >=1 full block of fp32 compresses ~4x
    raw, wire = qo.quantized_wire_bytes(256 * 64, 4)
    assert wire / raw <= 0.26


# ---------------------------------------------------------------------------
# host codec (state movement)
# ---------------------------------------------------------------------------

def test_encode_zlib_is_bitwise_lossless():
    rng = np.random.RandomState(1)
    for arr in (rng.randn(257, 3).astype(np.float32),
                rng.randint(-9, 9, (40,)).astype(np.int64),
                jnp.asarray(rng.randn(64), jnp.bfloat16)):
        host = np.asarray(arr)
        enc = qo.encode_array(host, mode="zlib")
        back = qo.decode_array(enc)
        assert back.dtype == host.dtype and back.shape == host.shape
        assert np.array_equal(back.view(np.uint8), host.view(np.uint8))
        assert enc["raw_bytes"] == host.nbytes


def test_encode_q8_envelope_and_int_fallback():
    rng = np.random.RandomState(2)
    x = rng.randn(4096).astype(np.float32)
    enc = qo.encode_array(x, mode="q8")
    assert enc["mode"] == "q8"
    assert enc["wire_bytes"] <= 0.30 * enc["raw_bytes"]
    back = qo.decode_array(enc)
    assert np.max(np.abs(back - x)) <= np.abs(x).max() / 127.0
    # integers must never go lossy: q8 falls back to zlib
    ints = rng.randint(0, 5, (100,)).astype(np.int32)
    enc2 = qo.encode_array(ints, mode="q8")
    assert enc2["mode"] == "zlib"
    np.testing.assert_array_equal(qo.decode_array(enc2), ints)


def test_encode_rejects_unknown_mode():
    with pytest.raises(ValueError, match="mode"):
        qo.encode_array(np.zeros(4, np.float32), mode="lz99")


# ---------------------------------------------------------------------------
# quantized collective kernels
# ---------------------------------------------------------------------------

def _mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def test_quantized_psum_matches_numpy_reference():
    """quantized_psum == sum over shards of independently dequantized
    per-shard contributions (the EQuARX accuracy model), bit-for-bit
    replicated on every shard."""
    from paddle_tpu.distributed.mesh import shard_map_unchecked
    from jax.sharding import PartitionSpec as P
    n = 4
    mesh = _mesh(n)
    rng = np.random.RandomState(3)
    x = rng.randn(n, 300).astype(np.float32)

    def local(xs):
        return co.quantized_psum(xs[0], "dp", block_size=64)

    fn = shard_map_unchecked(local, mesh, P("dp"), P())
    got = np.asarray(jax.jit(fn)(jnp.asarray(x)))
    want = np.zeros(300, np.float32)
    for i in range(n):
        q, s = qo.np_block_quantize(x[i], block_size=64)
        want += qo.np_block_dequantize(q, s, (300,), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # mean variant
    fn_m = shard_map_unchecked(
        lambda xs: co.quantized_psum(xs[0], "dp", block_size=64,
                                     mean=True),
        mesh, P("dp"), P())
    got_m = np.asarray(jax.jit(fn_m)(jnp.asarray(x)))
    np.testing.assert_allclose(got_m, want / n, rtol=1e-5, atol=1e-6)


def test_quant_allreduce_op_identity_outside_shard_map():
    """Same contract as every collective kernel: no bound axis -> no-op,
    so the one program runs anywhere."""
    from paddle_tpu.ops.registry import get_op

    class Ctx:
        bound_axes = ()

    x = jnp.asarray(np.arange(6.0, dtype=np.float32))
    out = get_op("c_allreduce_sum_quant").fn(
        Ctx(), {"X": [x]}, {"axis_name": "dp"})
    np.testing.assert_array_equal(np.asarray(out["Out"]), np.asarray(x))


def test_quant_allreduce_op_inside_shard_map():
    from paddle_tpu.distributed.mesh import shard_map_unchecked
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.ops.registry import get_op
    n = 4
    mesh = _mesh(n)

    class Ctx:
        bound_axes = ("dp",)

    rng = np.random.RandomState(4)
    x = rng.randn(n, 128).astype(np.float32)

    def local(xs):
        return get_op("c_allreduce_sum_quant").fn(
            Ctx(), {"X": [xs[0]]},
            {"axis_name": "dp", "block_size": 64})["Out"]

    fn = shard_map_unchecked(local, mesh, P("dp"), P())
    got = np.asarray(jax.jit(fn)(jnp.asarray(x)))
    exact = x.sum(axis=0)
    # quantization error bounded by the per-shard block bound, summed
    bound = sum(np.abs(x[i]).max() / 127.0 for i in range(n))
    assert np.max(np.abs(got - exact)) <= bound


def test_sync_context_byte_accounting_and_min_size():
    ctx = co.QuantizedSyncContext("dp", block_size=256, bits=8)
    # large grad: quantized accounting
    g = jnp.zeros((256 * 4,), jnp.float32)
    raw, wire = qo.quantized_wire_bytes(256 * 4, 4, 256, 8)
    # call through a traced context so lax collectives have an axis —
    # easiest is to check accounting only, via the sizes
    assert ctx.min_size == 256
    # small grads ride exact: raw == wire contribution
    import jax as _jax
    from paddle_tpu.distributed.mesh import shard_map_unchecked
    from jax.sharding import PartitionSpec as P
    mesh = _mesh(2)

    def local(a, b):
        return ctx.sync("big", a[0]), ctx.sync("small", b[0])

    fn = shard_map_unchecked(local, mesh, (P("dp"), P("dp")),
                             (P(), P()))
    big = jnp.ones((2, 1024), jnp.float32)
    small = jnp.ones((2, 8), jnp.float32)
    _jax.jit(fn)(big, small)
    assert ctx.synced == ["big"] and ctx.synced_exact == ["small"]
    assert ctx.raw_bytes == raw + 8 * 4
    assert ctx.wire_bytes == wire + 8 * 4
