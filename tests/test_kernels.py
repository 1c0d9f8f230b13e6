"""Pallas kernel sweeps: every kernel vs its ref.py oracle across shapes and
dtypes (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256), (384, 256, 384)])
def test_int8_matmul_shapes(rng, m, k, n):
    a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    sa = jnp.asarray(rng.uniform(1e-3, 1e-2, (m,)), jnp.float32)
    sb = jnp.asarray(rng.uniform(1e-3, 1e-2, (n,)), jnp.float32)
    np.testing.assert_allclose(ops.int8_matmul(a, b, sa, sb),
                               ref.int8_matmul(a, b, sa, sb), rtol=1e-6)


def test_int8_matmul_blocks(rng):
    a = jnp.asarray(rng.integers(-127, 128, (256, 256)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (256, 256)), jnp.int8)
    sa = jnp.ones((256,), jnp.float32)
    sb = jnp.ones((256,), jnp.float32)
    want = ref.int8_matmul(a, b, sa, sb)
    for bm, bn, bk in [(64, 64, 64), (128, 128, 256), (256, 256, 128)]:
        got = ops.int8_matmul(a, b, sa, sb, bm=bm, bn=bn, bk=bk)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_int8_matmul_exact_integer_accumulation(rng):
    # values whose products overflow int16 but not int32
    a = jnp.full((128, 128), 127, jnp.int8)
    b = jnp.full((128, 128), -127, jnp.int8)
    out = ops.int8_matmul(a, b, jnp.ones((128,)), jnp.ones((128,)))
    assert float(out[0, 0]) == 127 * -127 * 128


@pytest.mark.parametrize("shape", [(1, 8, 8, 8), (2, 16, 20, 32),
                                   (1, 32, 32, 128), (3, 24, 10, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_depthwise_sweep(rng, shape, dtype):
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=(3, 3, shape[-1])), dtype)
    got = ops.depthwise_conv3x3(x, w)
    want = ref.depthwise_conv3x3(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("s,d,causal", [(64, 32, True), (128, 64, True),
                                        (128, 64, False), (256, 32, True)])
def test_flash_attention_sweep(rng, s, d, causal):
    q = jnp.asarray(rng.normal(size=(2, 2, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, s, d)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, bq=s // 2, bk=s // 4)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_flash_attention_matches_model_attention(rng):
    """Kernel vs the jnp block-triangular schedule used by the LM stack."""
    from repro.configs import get_smoke
    from repro.models import layers as L
    from repro.models.params import materialize
    cfg = get_smoke("llama3.2-1b")
    B, S = 1, 64
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, S, cfg.head_dim)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, cfg.num_heads, S, cfg.head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, cfg.num_heads, S, cfg.head_dim)),
                    jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, bq=16, bk=16)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,nc,h,p,n", [(1, 4, 2, 8, 16), (2, 8, 4, 16, 8),
                                        (1, 12, 8, 64, 16)])
def test_ssd_scan_sweep(rng, b, nc, h, p, n):
    st = jnp.asarray(rng.normal(size=(b, nc, h, p, n)), jnp.float32)
    dc = jnp.asarray(rng.uniform(0.2, 1.0, (b, nc, h)), jnp.float32)
    np.testing.assert_allclose(ops.ssd_chunk_scan(st, dc),
                               ref.ssd_chunk_scan(st, dc),
                               rtol=1e-5, atol=1e-5)


def test_ssd_scan_matches_model_ssd(rng):
    """The kernel's recurrence must equal the jnp segsum form in the model:
    run the chunked SSD both ways on the same inputs."""
    from repro.models.layers import _segsum
    B, NC, H, P, N = 1, 4, 2, 4, 8
    states = jnp.asarray(rng.normal(size=(B, NC, H, P, N)), jnp.float32)
    chunk_sum = jnp.asarray(rng.uniform(-1.0, 0.0, (B, H, NC)), jnp.float32)
    # model form (lm SSD): decay_chunk via segsum of padded chunk sums
    pad = jnp.pad(chunk_sum, ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(_segsum(pad))
    init = jnp.zeros((B, 1, H, P, N))
    all_states = jnp.concatenate([init, states], axis=1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, all_states)
    want_prev = new_states[:, :-1]
    got = ops.ssd_chunk_scan(states, jnp.exp(chunk_sum).transpose(0, 2, 1))
    np.testing.assert_allclose(got, want_prev, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n", [(64, 64), (256, 768), (512, 128)])
def test_quantize_sweep(rng, m, n):
    x = jnp.asarray(rng.normal(size=(m, n)) * rng.uniform(0.1, 10), jnp.float32)
    q1, s1 = ops.quantize_rows(x)
    q2, s2 = ref.quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(s1, s2, rtol=1e-6)
    # reconstruction error bounded by scale/2 per element
    rec = np.asarray(q1, np.float32) * np.asarray(s1)[:, None]
    assert np.max(np.abs(rec - np.asarray(x))) <= np.max(np.asarray(s1)) * 0.51


def test_quantize_roundtrip_property(rng):
    from hypothesis import given, settings, strategies as st

    @given(st.integers(1, 8), st.integers(1, 300))
    @settings(max_examples=20, deadline=None)
    def inner(m, n):
        x = jnp.asarray(np.random.default_rng(m * 1000 + n)
                        .normal(size=(m, n)), jnp.float32)
        q, s = ref.quantize_rows(x)
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127
        assert bool(jnp.all(s > 0))

    inner()


# ---------------------------------------------------------------------------
# interpret mode follows the backend; shapes off the tiling raise
# ---------------------------------------------------------------------------

def test_interpret_default_env_override(monkeypatch):
    """No environment variable can switch the kernels' mode: interpret
    exactly when the backend is the CPU."""
    for value in ("1", "0", "true", "off"):
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", value)
        assert ops.interpret_default() == (jax.default_backend() == "cpu")


def test_kernel_parity_through_interpret_knob(rng):
    """int8_matmul / depthwise_conv vs the ref.py oracles with interpret
    mode passed explicitly (the calibration-harness execution path)."""
    from repro.kernels.depthwise_conv import depthwise_conv3x3_padded
    from repro.kernels.int8_matmul import int8_matmul
    a = jnp.asarray(rng.integers(-127, 128, (128, 256)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (256, 128)), jnp.int8)
    sa = jnp.asarray(rng.uniform(1e-3, 1e-2, (128,)), jnp.float32)
    sb = jnp.asarray(rng.uniform(1e-3, 1e-2, (128,)), jnp.float32)
    np.testing.assert_allclose(int8_matmul(a, b, sa, sb, interpret=True),
                               ref.int8_matmul(a, b, sa, sb), rtol=1e-6)
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 128)), jnp.float32)
    x_pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    np.testing.assert_allclose(
        depthwise_conv3x3_padded(x_pad, w, interpret=True),
        ref.depthwise_conv3x3(x, w), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 12, 10, 144), (1, 6, 5, 576)])
def test_depthwise_untiled_channels_run_the_kernel(rng, shape):
    """MobileNetV2's 144/576-channel layers (no 128-multiple divisor) and
    row counts off the 8-row tile run the Pallas kernel, not the oracle."""
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, shape[-1])), jnp.float32)
    assert "pallas_call" in str(jax.make_jaxpr(ops.depthwise_conv3x3)(x, w))
    np.testing.assert_allclose(ops.depthwise_conv3x3(x, w),
                               ref.depthwise_conv3x3(x, w),
                               rtol=1e-5, atol=1e-5)


def test_untileable_shape_raises(rng):
    a = jnp.zeros((200, 128), jnp.int8)
    b = jnp.zeros((128, 128), jnp.int8)
    with pytest.raises(ValueError, match="not a multiple"):
        ops.int8_matmul(a, b, jnp.ones((200,)), jnp.ones((128,)))
    with pytest.raises(ValueError, match="not a multiple"):
        ops.quantize_rows(jnp.zeros((300, 128)))
