"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
oracle across a shape x dtype sweep, per the assignment contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property-based kernel sweeps need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.decode_attention.decode_attention import kv_block
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fused_rmsnorm import ref as rn_ref
from repro.kernels.fused_rmsnorm.ops import rmsnorm
from repro.kernels.ssd import ref as ssd_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ssd import head_block, ssd_pallas

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 2e-2


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 256, 4, 2, 64),      # GQA
    (1, 200, 4, 4, 32),      # non-multiple seq
    (1, 384, 8, 1, 128),     # MQA, MXU-wide head
])
def test_flash_attention_vs_oracle(shape, dtype):
    B, S, H, KV, hd = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    scale = 1.0 / np.sqrt(hd)
    got = flash_attention(q, k, v, scale=scale, use_pallas=True,
                          interpret=True)
    want = flash_attention(q, k, v, scale=scale, use_pallas=False)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < _tol(dtype), f"{shape} {dtype}: {err}"


# ----------------------------------------------------------- decode attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,valid", [
    ((2, 512, 4, 2, 64), 301),
    ((1, 1024, 8, 8, 32), 1024),
    ((2, 640, 4, 1, 128), 17),
    ((2, 512, 4, 2, 64), 5),          # valid inside the first block
    ((1, 384, 4, 4, 80), 384),        # valid == S, stablelm-3b's width
    ((1, 200, 4, 4, 32), 150),        # S no multiple of 128: one block
    ((1, 384, 4, 4, 224), 300),       # zamba2-7b's width, blocks of 128
])
def test_decode_attention_vs_oracle(shape, valid, dtype):
    """Caches (B, KV, hd, S), sequence last, as decode keeps them."""
    B, S, H, KV, hd = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, hd, S), dtype)
    v = jax.random.normal(ks[2], (B, KV, hd, S), dtype)
    got = decode_attention(q, k, v, valid, scale=0.1, use_pallas=True,
                           interpret=True, block_k=128)
    want = decode_attention(q, k, v, valid, scale=0.1, use_pallas=False)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < _tol(dtype), f"{shape} valid={valid}: {err}"
    # positions past valid are masked: garbage there changes nothing
    junk = jnp.arange(S) >= valid
    k2, v2 = (jnp.where(junk, jnp.asarray(1e4, dtype), t) for t in (k, v))
    got2 = decode_attention(q, k2, v2, valid, scale=0.1, use_pallas=True,
                            interpret=True, block_k=128)
    assert np.array_equal(np.asarray(got2), np.asarray(got))


@pytest.mark.parametrize("S,block_k,want", [
    (1152, 512, 384), (1152, 128, 128), (640, 512, 128), (512, 512, 512),
    (2048, 512, 512), (1056, 512, 1056), (41, 512, 41), (128, 512, 128)])
def test_decode_attention_kv_block(S, block_k, want):
    """Blocks are whole 128-lane tiles dividing S, or all of S."""
    assert kv_block(S, block_k) == want


# ------------------------------------------------------------------------ ssd
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,chunk", [
    ((2, 128, 4, 1, 32, 64), 32),
    ((1, 96, 4, 2, 16, 32), 32),       # grouped B/C, ragged chunks
    ((1, 256, 2, 1, 64, 128), 128),    # production-like tile
    ((1, 256, 24, 1, 64, 128), 256),   # mamba2-130m heads, all in blocks
    ((1, 512, 8, 2, 16, 32), 128),     # grouped, state carried per block
    ((1, 256, 6, 2, 512, 512), 128),   # 3 heads a group, blocks of 1 head
])
def test_ssd_pallas_vs_naive(shape, chunk, dtype):
    B, S, H, G, P, N = shape
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, G, N), dtype)
    y0, h0 = ssd_ref.ssd_naive(x, dt, A, Bm, Cm)
    y1, h1 = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ry = (float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                - y0.astype(jnp.float32))))
          / (float(jnp.max(jnp.abs(y0.astype(jnp.float32)))) + 1e-9))
    rh = (float(jnp.max(jnp.abs(h1 - h0)))
          / (float(jnp.max(jnp.abs(h0))) + 1e-9))
    assert max(ry, rh) < (1e-5 if dtype == jnp.float32 else 3e-2), \
        f"{shape}: y={ry:.2e} h={rh:.2e}"


@pytest.mark.parametrize("shape,x_bytes,want", [
    ((24, 1, 64, 128, 256), 2, 24),    # mamba2-130m: one step a layer
    ((112, 2, 64, 64, 256), 2, 28),    # zamba2-7b: 2 steps a group, 1792 lanes
    ((6, 2, 512, 512, 128), 4, 1),     # VMEM forces blocks under a group
    ((4, 2, 16, 32, 32), 4, 2),        # no tileable block: the whole group
])
def test_ssd_head_block(shape, x_bytes, want):
    """The head block divides the group, never straddles one, and spans
    whole 128-lane tiles where the shapes allow it."""
    H, G, P, N, L = shape
    hb = head_block(H, G, P, N, L, x_bytes, x_bytes)
    assert hb == want
    assert (H // G) % hb == 0


def _ssd_inputs(B, S, H, G, P, N, dtype=jnp.float32):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, G, N), dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("shape,chunk", [
    ((1, 256, 24, 1, 64, 128), 128),   # mamba2-130m heads, two chunks
    ((2, 96, 4, 2, 16, 32), 32),       # grouped, ragged
])
def test_ssd_ops_final_state_only_when_asked(shape, chunk):
    """The prefill path asks for the final state and gets the XLA path's;
    the scoring forward does not, and its output is the same."""
    args = _ssd_inputs(*shape)
    y_x, h_x = ssd(*args, chunk=chunk, use_pallas=False)
    y_p, h_p = ssd(*args, chunk=chunk, use_pallas=True, interpret=True,
                   return_state=True)
    y_n, h_n = ssd(*args, chunk=chunk, use_pallas=True, interpret=True,
                   return_state=False)
    assert h_n is None
    assert ssd(*args, chunk=chunk, use_pallas=False,
               return_state=False)[1] is None
    np.testing.assert_array_equal(np.asarray(y_n), np.asarray(y_p))
    for got, want in ((h_p, h_x), (y_p, y_x)):
        assert (float(jnp.max(jnp.abs(got - want)))
                / (float(jnp.max(jnp.abs(want))) + 1e-9)) < 1e-5


@settings(max_examples=8, deadline=None)
@given(chunk=st.sampled_from([16, 32, 64, 96]),
       seq=st.integers(min_value=33, max_value=128))
def test_ssd_chunk_size_invariance(chunk, seq):
    """Property: the chunked algorithm is exact for ANY chunk size /
    sequence-length combination (incl. ragged final chunks)."""
    B, H, G, P, N = 1, 2, 1, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(seq), 5)
    x = jax.random.normal(ks[0], (B, seq, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, seq, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, seq, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, seq, G, N), jnp.float32)
    y0, h0 = ssd_ref.ssd_naive(x, dt, A, Bm, Cm)
    y1, h1 = ssd_ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    assert float(jnp.max(jnp.abs(y1 - y0))) / \
        (float(jnp.max(jnp.abs(y0))) + 1e-9) < 1e-5
    assert float(jnp.max(jnp.abs(h1 - h0))) / \
        (float(jnp.max(jnp.abs(h0))) + 1e-9) < 1e-5


def test_ssd_decode_step_consistency():
    """Running ssd_step over a sequence == ssd_naive."""
    B, S, H, G, P, N = 1, 24, 2, 1, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    y0, h0 = ssd_ref.ssd_naive(x, dt, A, Bm, Cm)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, h = ssd_ref.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        ys.append(y)
    y1 = jnp.stack(ys, axis=1)
    assert float(jnp.max(jnp.abs(y1 - y0))) < 1e-4
    assert float(jnp.max(jnp.abs(h - h0))) < 1e-4


def test_ssd_ops_dispatcher():
    B, S, H, G, P, N = 1, 64, 2, 1, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    y_x, _ = ssd(x, dt, A, Bm, Cm, chunk=32, use_pallas=False)
    y_p, _ = ssd(x, dt, A, Bm, Cm, chunk=32, use_pallas=True, interpret=True)
    assert float(jnp.max(jnp.abs(y_x - y_p))) < 1e-4


def test_ssd_chunked_grad_finite_at_strong_decay():
    """The XLA path trains mamba2: at a 256-token chunk with dt*A near -1.6
    per token, the decay above the diagonal overflows unless it is masked
    before exp, and the gradient turns nan."""
    B, S, H, G, P, N = 1, 512, 2, 1, 8, 16
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jnp.full((B, S, H), 0.1, jnp.float32)
    A = jnp.array([-16.0, -1.0], jnp.float32)
    Bm = jax.random.normal(ks[1], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[2], (B, S, G, N), jnp.float32)

    def loss(x, dt, A):
        y, h = ssd_ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=256)
        return jnp.sum(y ** 2) + jnp.sum(h ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, dt, A)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention",
                                    "ssd"])
def test_grad_through_pallas_kernel_names_it(kernel):
    """No Pallas kernel has a backward pass: jax.grad through one raises an
    error naming the kernel, not a bare AssertionError from inside JAX."""
    ks = jax.random.split(KEY, 5)
    if kernel == "ssd":
        B, S, H, G, P, N = 1, 64, 2, 1, 8, 16
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
        Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
        Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)

        def f(x):
            return ssd(x, dt, A, Bm, Cm, chunk=32, use_pallas=True,
                       interpret=True)[0].sum()
        x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    else:
        B, S, H, hd = 1, 32, 2, 32
        k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
        if kernel == "decode_attention":        # the decode cache's layout
            k, v = (jnp.transpose(t, (0, 2, 3, 1)) for t in (k, v))
        if kernel == "flash_attention":
            def f(q):
                return flash_attention(q, k, v, scale=hd ** -0.5,
                                       use_pallas=True, interpret=True).sum()
            x = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        else:
            def f(q):
                return decode_attention(q, k, v, S, scale=hd ** -0.5,
                                        use_pallas=True,
                                        interpret=True).sum()
            x = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    with pytest.raises(NotImplementedError, match=f"Pallas {kernel} kernel "
                       "has no backward pass"):
        jax.grad(f)(x)


# -------------------------------------------------------------------- rmsnorm
@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 70), d=st.sampled_from([32, 128, 256]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_fused_rmsnorm_property(rows, d, dtype):
    dt = jnp.dtype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), dt)
    w = jax.random.normal(jax.random.PRNGKey(d), (d,), dt) * 0.1
    got = rmsnorm(x, w, use_pallas=True, interpret=True)
    want = rn_ref.rmsnorm_ref(x, w)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < (1e-5 if dtype == "float32" else 0.05)
