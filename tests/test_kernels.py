"""Pallas kernel validation: interpret-mode execution against the pure-jnp
oracles in kernels/ref.py, over shape/dtype sweeps and hypothesis-driven
randomized cases (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _optional import given, settings, st  # hypothesis or skip-shims

from repro.kernels import ref
from repro.kernels.decode_attention import (block_k_for, cache_len_for,
                                            flash_decode)
from repro.kernels.flash_attention import (VMEM_BUDGET, _vmem_bytes,
                                           attention_plan, flash_attention)
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rwkv6 import rwkv6_scan_pallas

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _case(*shape, block=64):
    """An oracle case; cases at the 64-blocks keep their ids from before
    blocks were a parameter, the rest say ``default``."""
    name = "-".join(map(str, shape))
    return pytest.param(*shape, block,
                        id=name if block == 64 else f"{name}-default")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,hkv,d,causal,window,block", [
    _case(2, 128, 128, 4, 2, 64, True, None),
    _case(1, 256, 256, 2, 2, 64, True, 96),       # sliding window
    _case(2, 128, 256, 4, 4, 128, True, None),    # q_offset (chunked prefill)
    _case(1, 128, 128, 2, 1, 64, False, None),    # non-causal (cross-attn)
    _case(1, 64, 64, 8, 8, 128, True, None),
    # blocks from attention_plan
    _case(2, 128, 128, 4, 2, 32, True, None, block=None),
    _case(1, 256, 256, 2, 2, 32, False, None, block=None),
    _case(1, 512, 512, 3, 1, 32, True, None, block=None),     # GQA
    _case(1, 512, 512, 2, 2, 32, True, 200, block=None),
    _case(1, 1024, 1024, 4, 2, 32, True, None, block=None),
    _case(1, 1024, 1024, 2, 1, 32, False, None, block=None),
    _case(1, 1024, 1024, 2, 2, 32, True, 300, block=None),
    _case(1, 512, 1024, 2, 2, 32, True, None, block=None),    # q_offset
])
def test_flash_attention_vs_oracle(b, sq, sk, h, hkv, d, causal, window,
                                   block, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, sq, h, d), dtype)
    k = _rand(ks[1], (b, sk, hkv, d), dtype)
    v = _rand(ks[2], (b, sk, hkv, d), dtype)
    qo = sk - sq
    want = ref.attention_dense(q, k, v, causal=causal, window=window,
                               q_offset=qo)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=qo, block_q=block, block_k=block,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _check_gradients(s, d, block):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, s, 2, d), jnp.float32)
    k = _rand(ks[1], (1, s, 2, d), jnp.float32)
    v = _rand(ks[2], (1, s, 2, d), jnp.float32)

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, interpret=True, block_q=block,
                               block_k=block).sum()

    def f_ref(q, k, v):
        return ref.attention_dense(q, k, v).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_gradients_match_oracle():
    _check_gradients(64, 32, 32)


def test_flash_attention_gradients_at_default_blocks():
    _check_gradients(1024, 16, None)


@pytest.mark.parametrize("s", [64, 100, 128, 256, 384, 512, 640, 1024,
                               2048, 4096])
@pytest.mark.parametrize("d", [64, 128])
def test_attention_plan_blocks_divide_the_sequence(s, d):
    plan = attention_plan(s, s, d)
    assert s % plan.block_q == 0 and s % plan.block_k == 0
    assert plan.steps == (s // plan.block_q) * (s // plan.block_k)
    # the largest blocks that tile: the whole sequence up to 512
    assert plan.block_q == plan.block_k == (s if s <= 512 else 512
                                            if s % 512 == 0 else 128)


@pytest.mark.parametrize("s", [136, 300, 1000])
def test_attention_plan_refuses_sequences_off_the_lanes(s):
    # past 128 positions a block is a multiple of 128, as the kernel's
    # score tiles need
    with pytest.raises(ValueError, match="whole number"):
        attention_plan(s, s, 64)


def test_attention_plan_takes_both_blocks_or_neither():
    with pytest.raises(ValueError, match="both blocks"):
        attention_plan(512, 512, 64, block_q=128)


@pytest.mark.parametrize("s,block,steps,computed", [
    (128, None, 1, 1),
    (512, None, 1, 1),
    (1024, None, 4, 3),
    (2048, None, 16, 10),
    (512, 128, 16, 10),     # the kernel's blocks before they came from
    (1024, 128, 64, 36),    # the shape: still what a caller passing them gets
    (512, 256, 4, 3),
])
def test_attention_plan_computes_the_lower_triangle(s, block, steps,
                                                    computed):
    plan = attention_plan(s, s, 64, causal=True, block_q=block,
                          block_k=block)
    n = s // plan.block_k
    assert plan.block_q == plan.block_k
    assert (plan.steps, plan.computed) == (steps, computed)
    assert plan.computed == n * (n + 1) // 2
    full = attention_plan(s, s, 64, causal=False, block_q=block,
                          block_k=block)
    assert full.computed == full.steps == steps


def test_attention_plan_skips_blocks_outside_the_window():
    # rows of q block i see columns (64 i - 96, 64 i + 63]: the diagonal
    # block and the one or two before it
    plan = attention_plan(256, 256, 64, causal=True, window=96,
                          block_q=64, block_k=64)
    assert (plan.steps, plan.computed) == (16, 1 + 2 + 3 + 3)
    # a q block past the first of the keys (chunked prefill) sees every
    # block up to its own
    plan = attention_plan(256, 512, 64, causal=True, q_offset=256,
                          block_q=128, block_k=128)
    assert (plan.steps, plan.computed) == (8, 3 + 4)


def test_attention_plan_shrinks_blocks_to_its_vmem_budget():
    plan = attention_plan(1024, 1024, 1024)
    assert plan.block_q * plan.block_k < 512 * 512
    assert _vmem_bytes(plan.block_q, plan.block_k, 1024, 2) <= VMEM_BUDGET


@given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 1),
       st.booleans())
@settings(max_examples=10, deadline=None)
def test_flash_attention_hypothesis(b, hkv_pow, grp_pow, causal):
    hkv = 2 ** (hkv_pow - 1)
    h = hkv * (2 ** grp_pow)
    ks = jax.random.split(jax.random.PRNGKey(b * 17 + h), 3)
    q = _rand(ks[0], (b, 64, h, 32), jnp.float32)
    k = _rand(ks[1], (b, 64, hkv, 32), jnp.float32)
    v = _rand(ks[2], (b, 64, hkv, 32), jnp.float32)
    want = ref.attention_dense(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_reference_matches_dense():
    """The XLA fallback (dry-run path) equals the oracle too."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (2, 128, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 128, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 128, 2, 32), jnp.float32)
    want = ref.attention_dense(q, k, v, causal=True, window=50)
    got = ref.attention_chunked(q, k, v, causal=True, window=50, chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,smax,h,hkv,d,clen,window", [
    (2, 256, 4, 2, 64, 200, None),
    (1, 512, 8, 8, 64, 512, None),
    (2, 256, 4, 1, 128, 100, None),
    (2, 256, 4, 2, 64, 256, 128),            # ring-buffer window
    (3, 128, 6, 2, 64, 64, None),
])
def test_flash_decode_vs_oracle(b, smax, h, hkv, d, clen, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (b, h, d), dtype)
    kc = _rand(ks[1], (b, smax, hkv, d), dtype)
    vc = _rand(ks[2], (b, smax, hkv, d), dtype)
    want = ref.decode_attention(q, kc, vc, clen, window=window)
    got = flash_decode(q, kc, vc, clen, window=window, block_k=64,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_decode_per_batch_lengths():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (3, 4, 32), jnp.float32)
    kc = _rand(ks[1], (3, 128, 2, 32), jnp.float32)
    vc = _rand(ks[2], (3, 128, 2, 32), jnp.float32)
    lens = jnp.array([10, 64, 128], jnp.int32)
    want = ref.decode_attention(q, kc, vc, lens)
    got = flash_decode(q, kc, vc, lens, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,smax,block", [
    (200, 200, 200),          # one block: the whole cache
    (512, 512, 512),
    (640, 640, 128),          # 512 + 128: blocks shrink, the cache does not
    (700, 768, 256),
    (1024, 1024, 512),
])
def test_cache_len_and_block_for(n, smax, block):
    assert cache_len_for(n) == smax
    assert block_k_for(smax) == block


def test_block_k_for_refuses_an_untiled_cache():
    with pytest.raises(ValueError, match="cache_len_for"):
        block_k_for(600)


def test_flash_decode_tiles_a_cache_off_the_default_block():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(ks[0], (2, 9, 64), jnp.float32)
    kc = _rand(ks[1], (2, 640, 3, 64), jnp.float32)
    vc = _rand(ks[2], (2, 640, 3, 64), jnp.float32)
    lens = jnp.array([513, 640], jnp.int32)
    want = ref.decode_attention(q, kc, vc, lens)
    got = flash_decode(q, kc, vc, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6 / mamba recurrences
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,d,chunk", [
    (2, 64, 2, 16, 16), (1, 128, 4, 32, 32), (2, 32, 1, 64, 8)])
def test_rwkv6_kernel_vs_oracle(b, s, h, d, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r = _rand(ks[0], (b, s, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, h, d), jnp.float32) * 0.3
    v = _rand(ks[2], (b, s, h, d), jnp.float32)
    w = jax.nn.sigmoid(_rand(ks[3], (b, s, h, d), jnp.float32))  # decay<1
    u = _rand(ks[4], (h, d), jnp.float32) * 0.1
    want_o, want_s = ref.rwkv6_scan(r, k, v, w, u)
    got_o, got_s = rwkv6_scan_pallas(r, k, v, w, u, chunk=chunk,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_kernel_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    b, s, h, d = 1, 32, 2, 16
    r = _rand(ks[0], (b, s, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, h, d), jnp.float32) * 0.3
    v = _rand(ks[2], (b, s, h, d), jnp.float32)
    w = jax.nn.sigmoid(_rand(ks[3], (b, s, h, d), jnp.float32))
    u = _rand(ks[4], (h, d), jnp.float32) * 0.1
    s0 = _rand(ks[0], (b, h, d, d), jnp.float32)
    want_o, want_s = ref.rwkv6_scan(r, k, v, w, u, s0=s0)
    got_o, got_s = rwkv6_scan_pallas(r, k, v, w, u, s0=s0, chunk=8,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bt,s,di,n,chunk,bd", [
    (2, 64, 64, 16, 16, 32), (1, 32, 128, 8, 8, 128), (2, 32, 32, 4, 32, 32)])
def test_mamba_kernel_vs_oracle(bt, s, di, n, chunk, bd):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = _rand(ks[0], (bt, s, di), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (bt, s, di), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (di, n), jnp.float32) * 0.5)
    B = _rand(ks[3], (bt, s, n), jnp.float32)
    C = _rand(ks[4], (bt, s, n), jnp.float32)
    D = jnp.ones((di,), jnp.float32)
    want_y, want_h = ref.mamba_scan(x, dt, A, B, C, D)
    got_y, got_h = mamba_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                                     block_d=bd, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-4)


def test_mamba_kernel_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    bt, s, di, n = 1, 16, 32, 8
    x = _rand(ks[0], (bt, s, di), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (bt, s, di), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (di, n), jnp.float32) * 0.5)
    B = _rand(ks[3], (bt, s, n), jnp.float32)
    C = _rand(ks[4], (bt, s, n), jnp.float32)
    D = jnp.ones((di,), jnp.float32)
    h0 = _rand(ks[5], (bt, di, n), jnp.float32)
    want_y, want_h = ref.mamba_scan(x, dt, A, B, C, D, h0=h0)
    got_y, got_h = mamba_scan_pallas(x, dt, A, B, C, D, h0=h0, chunk=8,
                                     block_d=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: model with Pallas kernels == model with reference ops
# ---------------------------------------------------------------------------

def test_model_forward_with_pallas_kernels():
    from repro.configs import get
    from repro.kernels import ops
    from repro.models import forward, init_params
    cfg = get("olmo-1b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    want = forward(cfg, params, toks)
    ops.set_use_pallas(True, interpret=True)
    try:
        got = forward(cfg, params, toks)
    finally:
        ops.set_use_pallas(None)
    # bf16 end-to-end accumulation over 4 layers: ~2% of logit scale
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=0.1)
