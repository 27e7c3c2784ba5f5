"""Differential suite: sliced (resumable) kernel execution is value-
identical to the whole-grid kernels, for all four kernels, across slice
widths — interpret-mode Pallas on CPU.  Also pins the carry resume
contract: a snapshot taken mid-op and resumed (including through a
checkpoint save/restore roundtrip) reproduces the unsliced result."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode, flash_decode_sliced
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_sliced)
from repro.kernels.mamba_scan import mamba_scan_pallas, mamba_scan_sliced
from repro.kernels.rwkv6 import rwkv6_scan_pallas, rwkv6_scan_sliced
from repro.sched import latest_carry, save_carry


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def _attn_inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (2, 128, 4, 32))
    k = _rand(ks[1], (2, 128, 2, 32))
    v = _rand(ks[2], (2, 128, 2, 32))
    return q, k, v


def _decode_inputs():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (3, 4, 32))
    kc = _rand(ks[1], (3, 256, 2, 32))
    vc = _rand(ks[2], (3, 256, 2, 32))
    lens = jnp.array([10, 200, 256], jnp.int32)
    return q, kc, vc, lens


def _mamba_inputs():
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    bt, s, di, n = 2, 64, 32, 8
    x = _rand(ks[0], (bt, s, di))
    dt = jax.nn.softplus(_rand(ks[1], (bt, s, di)))
    A = -jnp.exp(_rand(ks[2], (di, n)) * 0.5)
    B = _rand(ks[3], (bt, s, n))
    C = _rand(ks[4], (bt, s, n))
    D = jnp.ones((di,), jnp.float32)
    h0 = _rand(ks[5], (bt, di, n))
    return x, dt, A, B, C, D, h0


def _rwkv_inputs():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, d = 1, 64, 2, 16
    r = _rand(ks[0], (b, s, h, d))
    k = _rand(ks[1], (b, s, h, d)) * 0.3
    v = _rand(ks[2], (b, s, h, d))
    w = jax.nn.sigmoid(_rand(ks[3], (b, s, h, d)))
    u = _rand(ks[4], (h, d)) * 0.1
    return r, k, v, w, u


# ---------------------------------------------------------------------------
# sliced == unsliced (pinned numerical identity), multiple slice widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_slice", [1, 2, 3, 4])
@pytest.mark.parametrize("window", [None, 96])
def test_flash_attention_sliced_identity(kv_slice, window):
    q, k, v = _attn_inputs()
    want = flash_attention(q, k, v, causal=True, window=window,
                           block_q=64, block_k=32, interpret=True)
    op = flash_attention_sliced(q, k, v, causal=True, window=window,
                                block_q=64, block_k=32, kv_slice=kv_slice,
                                interpret=True)
    got = op.run()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and both match the dense oracle
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.attention_dense(
            q, k, v, causal=True, window=window)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_slice", [1, 3, 8])
def test_flash_decode_sliced_identity(kv_slice):
    q, kc, vc, lens = _decode_inputs()
    want = flash_decode(q, kc, vc, lens, block_k=32, interpret=True)
    op = flash_decode_sliced(q, kc, vc, lens, block_k=32,
                             kv_slice=kv_slice, interpret=True)
    got = op.run()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.decode_attention(q, kc, vc, lens)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slice_chunks", [1, 2, 3, 8])
def test_mamba_sliced_identity(slice_chunks):
    x, dt, A, B, C, D, h0 = _mamba_inputs()
    want_y, want_h = mamba_scan_pallas(x, dt, A, B, C, D, h0=h0, chunk=8,
                                       block_d=32, interpret=True)
    op = mamba_scan_sliced(x, dt, A, B, C, D, h0=h0, chunk=8, block_d=32,
                           slice_chunks=slice_chunks, interpret=True)
    got_y, got_h = op.run()
    np.testing.assert_array_equal(np.asarray(got_y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))


@pytest.mark.parametrize("slice_chunks", [1, 2, 3, 8])
def test_rwkv6_sliced_identity(slice_chunks):
    r, k, v, w, u = _rwkv_inputs()
    want_o, want_s = rwkv6_scan_pallas(r, k, v, w, u, chunk=8,
                                       interpret=True)
    op = rwkv6_scan_sliced(r, k, v, w, u, chunk=8,
                           slice_chunks=slice_chunks, interpret=True)
    got_o, got_s = op.run()
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


# ---------------------------------------------------------------------------
# the carry resume contract (preemption / checkpoint mid-op)
# ---------------------------------------------------------------------------

def test_attention_carry_resume_after_snapshot(tmp_path):
    """Run half the slices, checkpoint the carry to disk, rebuild the op
    from scratch (as a restarted process would) and resume: identical to
    the uninterrupted run."""
    q, k, v = _attn_inputs()

    def make_op():
        return flash_attention_sliced(q, k, v, block_q=64, block_k=32,
                                      kv_slice=1, interpret=True)

    op = make_op()
    assert op.n_slices == 4
    carry = op.init()
    for i in range(2):
        carry = op.step(carry, i)
    save_carry(str(tmp_path), "attn", 2, carry)

    op2 = make_op()
    idx, restored = latest_carry(str(tmp_path), "attn", op2.init())
    assert idx == 2
    got = op2.run(carry=restored, start=idx)
    want = flash_attention(q, k, v, block_q=64, block_k=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rwkv6_carry_resume_mid_sequence():
    r, k, v, w, u = _rwkv_inputs()
    op = rwkv6_scan_sliced(r, k, v, w, u, chunk=8, slice_chunks=2,
                           interpret=True)
    carry = op.init()
    carry = op.step(carry, 0)   # first 2 time chunks
    got_o, got_s = op.run(carry=carry, start=1)
    want_o, want_s = rwkv6_scan_pallas(r, k, v, w, u, chunk=8,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


# ---------------------------------------------------------------------------
# ops-layer dispatch: the sliced entry points on both backends
# ---------------------------------------------------------------------------

def test_ops_sliced_pallas_dispatch():
    q, k, v = _attn_inputs()
    ops.set_use_pallas(True, interpret=True)
    try:
        got = ops.attention_sliced(q, k, v, block_q=64, block_k=32,
                                   kv_slice=2).run()
    finally:
        ops.set_use_pallas(None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.attention_dense(q, k, v)),
        rtol=2e-5, atol=2e-5)


def test_ops_sliced_reference_path_recurrences():
    """With Pallas off, mamba/rwkv slicing runs the pure-jnp reference per
    window — identical to the whole-sequence reference."""
    x, dt, A, B, C, D, h0 = _mamba_inputs()
    ops.set_use_pallas(False)
    try:
        got_y, got_h = ops.mamba_scan_sliced(x, dt, A, B, C, D, h0=h0,
                                             chunk=8, slice_chunks=3).run()
        want_y, want_h = ref.mamba_scan(x, dt, A, B, C, D, h0=h0)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                                   rtol=1e-5, atol=1e-5)

        r, k, v, w, u = _rwkv_inputs()
        got_o, got_s = ops.rwkv6_scan_sliced(r, k, v, w, u, chunk=8,
                                             slice_chunks=2).run()
        want_o, want_s = ref.rwkv6_scan(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-5)
    finally:
        ops.set_use_pallas(None)


def test_sliced_slice_count_contract():
    q, k, v = _attn_inputs()
    # 128 kv positions / block_k=32 -> 4 kv blocks
    for kv_slice, n in [(1, 4), (2, 2), (3, 2), (4, 1), (100, 1)]:
        op = flash_attention_sliced(q, k, v, block_q=64, block_k=32,
                                    kv_slice=kv_slice, interpret=True)
        assert op.n_slices == n, (kv_slice, op.n_slices)


@pytest.mark.parametrize("value", ["off", "interpret"])
def test_repro_pallas_cpu_switch_is_refused_on_tpu(monkeypatch, value):
    """``REPRO_PALLAS=off|interpret`` steers CPU tests; on a TPU backend
    it would hide the compiled kernels, so there it raises."""
    monkeypatch.setenv("REPRO_PALLAS", value)
    assert ops.use_pallas() == (value == "interpret")
    assert ops.interpret_mode() == (value == "interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU-test switch"):
        ops.use_pallas()
    with pytest.raises(RuntimeError, match="CPU-test switch"):
        ops.interpret_mode()


def test_repro_pallas_unknown_value_is_refused(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "on")
    with pytest.raises(ValueError, match="REPRO_PALLAS"):
        ops.use_pallas()
