"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional dep, skips cleanly

from repro.core import ethernet_ipv4_udp, compressed_protocol, Field, Protocol


# ----------------------------------------------------------------- quant_pack

@pytest.mark.parametrize("shape", [(8, 128), (256, 384), (64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_ref(shape, dtype):
    from repro.kernels.quant_pack import kernel, ref
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    q1, s1 = kernel.quantize(x, interpret=True)
    q2, s2 = ref.quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_quantize_roundtrip_error_bounded():
    from repro.kernels.quant_pack import kernel
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)
    q, s = kernel.quantize(x, interpret=True)
    xr = kernel.dequantize(q, s, interpret=True)
    group_max = np.abs(np.asarray(x)).reshape(128, 2, 128).max(-1)
    bound = np.repeat(group_max / 127.0, 128, axis=-1).reshape(128, 256) * 0.5 + 1e-6
    assert (np.abs(np.asarray(xr) - np.asarray(x)) <= bound).all()


def test_compress_arbitrary_shapes():
    from repro.kernels.quant_pack.ops import compress, decompress, compression_ratio
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 37), jnp.float32)
    q, s, meta = compress(x)
    xr = decompress(q, s, meta)
    assert xr.shape == x.shape
    assert compression_ratio(x) > 3.0
    assert float(jnp.abs(xr - x).max()) < 0.05


# --------------------------------------------------------------------- parser

@pytest.mark.parametrize("proto_fn,fields", [
    (ethernet_ipv4_udp, ["eth_dst", "ip_tos", "ip_dst", "udp_dst"]),
    (lambda: compressed_protocol(addr_bits=4, length_bits=6), ["dst", "src", "len"]),
])
@pytest.mark.parametrize("n", [1, 7, 300])
def test_parser_kernel_matches_ref(proto_fn, fields, n):
    from repro.kernels.parser.ops import parse_headers
    from repro.kernels.parser.ref import parse_ref
    from repro.switch.parser import pack_header_words
    proto = proto_fn()
    rng = np.random.default_rng(0)
    vals = {f.name: rng.integers(0, min(1 << f.bits, 1 << 31), n, dtype=np.uint64)
            for f in proto.fields}
    words = jnp.asarray(pack_header_words(proto, vals))
    out_k = parse_headers(proto, fields, words, use_pallas=True, interpret=True)
    out_r = parse_ref(proto, fields, words)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@st.composite
def _proto_and_vals(draw):
    nf = draw(st.integers(2, 6))
    fields = [Field(f"f{i}", draw(st.integers(1, 31))) for i in range(nf)]
    proto = Protocol("rand", fields)
    vals = {f.name: np.array([draw(st.integers(0, (1 << f.bits) - 1))
                              for _ in range(3)], dtype=np.uint64)
            for f in fields}
    return proto, vals


@given(_proto_and_vals())
@settings(max_examples=15, deadline=None)
def test_parser_kernel_random_protocols(pv):
    from repro.kernels.parser.ops import parse_headers
    from repro.switch.parser import pack_header_words
    proto, vals = pv
    words = jnp.asarray(pack_header_words(proto, vals))
    names = [f.name for f in proto.fields]
    out = parse_headers(proto, names, words, use_pallas=True, interpret=True)
    for i, f in enumerate(proto.fields):
        np.testing.assert_array_equal(np.asarray(out[:, i]),
                                      vals[f.name].astype(np.uint32))


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("s,d,hq,hkv", [(128, 64, 4, 4), (256, 64, 8, 2), (256, 128, 4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(s, d, hq, hkv, causal):
    from repro.kernels.flash_attention.ops import attention_reference, flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, hq, s, d), jnp.float32)
    k = jax.random.normal(k2, (2, hkv, s, d), jnp.float32)
    v = jax.random.normal(k3, (2, hkv, s, d), jnp.float32)
    o1 = flash_attention(q, k, v, causal=causal, block_q=64, block_k=128,
                         interpret=True)
    o2 = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5, rtol=3e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import attention_reference, flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 4, 128, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 4, 128, 64), jnp.bfloat16)
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = attention_reference(q, k, v)
    assert float(jnp.abs(o1.astype(jnp.float32) - o2.astype(jnp.float32)).max()) < 0.05


def test_xla_blockwise_matches_pallas():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import blockwise_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k1, (2, 4, 256, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 2, 256, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 2, 256, 64), jnp.float32)
    o1 = blockwise_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o2 = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)


# ------------------------------------------------------------------------ ssd

@pytest.mark.parametrize("s,p,n,chunk", [(128, 32, 16, 32), (256, 64, 32, 64),
                                         (256, 64, 128, 128)])
def test_ssd_kernel_and_chunked_match_ref(s, p, n, chunk):
    from repro.kernels.ssd.kernel import ssd_scan
    from repro.kernels.ssd.ops import ssd_chunked, ssd_reference
    kk = jax.random.split(jax.random.PRNGKey(3), 5)
    bh = 2
    x = jax.random.normal(kk[0], (bh, s, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(kk[1], (bh, s))) * 0.1
    a = -jnp.exp(jax.random.normal(kk[2], (bh,)) * 0.3)
    b = jax.random.normal(kk[3], (bh, s, n), jnp.float32)
    c = jax.random.normal(kk[4], (bh, s, n), jnp.float32)
    ref = ssd_reference(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(ssd_chunked(x, dt, a, b, c, chunk=chunk)),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(ssd_scan(x, dt, a, b, c, chunk=chunk,
                                                    interpret=True)),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_ssd_decode_step_matches_prefill_state():
    """Chunked prefill final state == running the sequential decode steps."""
    from repro.kernels.ssd.ops import ssd_chunked, ssd_decode_step
    kk = jax.random.split(jax.random.PRNGKey(7), 5)
    bh, s, p, n = 2, 64, 16, 8
    x = jax.random.normal(kk[0], (bh, s, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(kk[1], (bh, s))) * 0.1
    a = -jnp.exp(jax.random.normal(kk[2], (bh,)) * 0.3)
    b = jax.random.normal(kk[3], (bh, s, n), jnp.float32)
    c = jax.random.normal(kk[4], (bh, s, n), jnp.float32)
    _, final = ssd_chunked(x, dt, a, b, c, chunk=16, return_state=True)
    state = jnp.zeros((bh, p, n))
    for t in range(s):
        state, _ = ssd_decode_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
    np.testing.assert_allclose(np.asarray(final), np.asarray(state), atol=1e-3)


# ---------------------------------------------------------------- iSLIP

@pytest.mark.parametrize("n,iters", [(4, 1), (8, 2), (16, 3)])
def test_islip_kernel_matches_lax_scheduler(n, iters):
    from repro.kernels.islip.ops import islip_schedule
    rng = np.random.default_rng(1)
    B = 16
    req = jnp.asarray(rng.integers(0, 2, (B, n, n)), jnp.int32)
    g = jnp.asarray(rng.integers(0, n, (B, n)), jnp.int32)
    a = jnp.asarray(rng.integers(0, n, (B, n)), jnp.int32)
    m1, g1, a1 = islip_schedule(req, g, a, iters=iters, use_pallas=True, interpret=True)
    m2, g2, a2 = islip_schedule(req, g, a, iters=iters, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


@given(st.integers(0, 2**16 - 1), st.integers(1, 3))
@settings(max_examples=25, deadline=None)
def test_islip_kernel_match_validity_property(bits, iters):
    from repro.kernels.islip.ops import islip_schedule
    n = 4
    req = jnp.asarray([(bits >> i) & 1 for i in range(n * n)], jnp.int32).reshape(1, n, n)
    g = jnp.zeros((1, n), jnp.int32)
    a = jnp.zeros((1, n), jnp.int32)
    m, _, _ = islip_schedule(req, g, a, iters=iters, use_pallas=True, interpret=True)
    m = np.asarray(m[0])
    assert (m.sum(0) <= 1).all() and (m.sum(1) <= 1).all()
    assert not (m & ~np.asarray(req[0]).astype(bool)).any()
    if np.asarray(req[0]).any():
        assert m.any()
