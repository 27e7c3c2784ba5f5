"""Flash-decode kernel (Pallas, TPU target): single-token attention over a
(possibly very long) KV cache.

Grid = (batch, kv_blocks): each step loads one KV block into VMEM, computes
partial (max, sum, acc) for *all* heads of that batch element (the
online-softmax merge), and flushes q's output at the last block.  GQA is
exploited natively: the score matmul is (G q-heads x D) @ (D x bk) per KV
head — q heads grouped by their kv head, so the cache is read once.

Invalid tail entries (cache_len <= idx) and sliding windows are masked via
the per-batch length vector, scalar-prefetched into SMEM (a rank-1 VMEM
block of one length per batch element is refused by the TPU compiler once
the batch exceeds 1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.segments import SlicedOp, n_slices_for

NEG_INF = -1e30

#: largest KV block of one grid step; a cache longer than it is tiled by
#: the largest of BLOCK_K, BLOCK_K / 2, BLOCK_K / 4 that divides it
BLOCK_K = 512


def cache_len_for(n: int) -> int:
    """The least cache length >= ``n`` that ``flash_decode``'s blocks
    tile: ``n`` itself up to one block, else a multiple of BLOCK_K / 4."""
    step = BLOCK_K // 4
    return n if n <= BLOCK_K else -(-n // step) * step


def block_k_for(smax: int, block_k: int = BLOCK_K) -> int:
    """The KV block for a cache of ``smax`` positions: the whole cache up
    to ``block_k``, else the largest of ``block_k``, ``block_k / 2`` and
    ``block_k / 4`` that divides it."""
    if smax <= block_k:
        return smax
    for bk in (block_k, block_k // 2, block_k // 4):
        if smax % bk == 0:
            return bk
    raise ValueError(f"a cache of {smax} positions is no whole number of "
                     f"{block_k // 4}-position blocks: size it with "
                     f"cache_len_for")


def _block_update(len_ref, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, window: Optional[int], cols_base,
                  block_k: int, n_heads: int, n_kv_heads: int):
    """One online-softmax KV-block merge on the VMEM carry — shared by the
    whole-grid kernel and the sliced (resumable) kernel.  ``len_ref`` is
    the whole (B,) length vector in SMEM, indexed by the batch grid axis."""
    g = n_heads // n_kv_heads
    q = q_ref[0].astype(jnp.float32)              # (H, D)
    k = k_ref[0].astype(jnp.float32)              # (bk, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    cache_len = len_ref[pl.program_id(0)]

    cols = cols_base + jax.lax.iota(jnp.int32, block_k)
    valid = cols < cache_len
    if window is not None:
        valid &= cols >= cache_len - window

    # scores per kv head group: (Hkv, G, D) x (Hkv, bk, D) -> (Hkv, G, bk)
    qg = q.reshape(n_kv_heads, g, -1)
    kg = jnp.transpose(k, (1, 0, 2))              # (Hkv, bk, D)
    s = jax.lax.dot_general(
        qg, kg, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (Hkv, G, bk)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    s = s.reshape(n_heads, block_k)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])               # (H, bk)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    vg = jnp.transpose(v, (1, 0, 2))              # (Hkv, bk, D)
    pv = jax.lax.dot_general(
        p.reshape(n_kv_heads, g, block_k), vg,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # (Hkv, G, D)
    acc_scr[...] = acc_scr[...] * corr[:, None] \
        + pv.reshape(n_heads, -1)
    m_scr[...] = m_new


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window: Optional[int], block_k: int, n_kv: int,
            n_heads: int, n_kv_heads: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _block_update(len_ref, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  scale=scale, window=window, cols_base=ki * block_k,
                  block_k=block_k, n_heads=n_heads, n_kv_heads=n_kv_heads)

    @pl.when(ki == n_kv - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _carry_kernel(len_ref, q_ref, k_ref, v_ref, m0_ref, l0_ref, acc0_ref,
                  m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, window: Optional[int], kv_offset: int,
                  block_k: int, n_kv: int, n_heads: int, n_kv_heads: int):
    """Resumable slice over ``n_kv`` cache blocks starting at absolute
    position ``kv_offset``; the (m, l, acc) merge state is an explicit
    carry instead of being normalized away at the last block."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = m0_ref[0, 0]
        l_scr[...] = l0_ref[0, 0]
        acc_scr[...] = acc0_ref[0]

    _block_update(len_ref, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  scale=scale, window=window,
                  cols_base=kv_offset + ki * block_k,
                  block_k=block_k, n_heads=n_heads, n_kv_heads=n_kv_heads)

    @pl.when(ki == n_kv - 1)
    def _flush():
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        acc_ref[0] = acc_scr[...]


def _scratch(h: int, d: int):
    """VMEM merge state of one batch element: running max, sum, acc."""
    return [pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32)]


def flash_decode(q, k_cache, v_cache, cache_len, *,
                 window: Optional[int] = None, block_k: int = BLOCK_K,
                 interpret: bool = False):
    """q: (B, H, D); caches: (B, Smax, Hkv, D); cache_len: (B,) or scalar.
    Returns (B, H, D)."""
    b, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    block_k = block_k_for(smax, block_k)
    n_kv = smax // block_k
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, window=window, block_k=block_k,
        n_kv=n_kv, n_heads=h, n_kv_heads=hkv)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kv),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda b_, k_, _: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, hkv, d), lambda b_, k_, _:
                             (b_, k_, 0, 0)),
                pl.BlockSpec((1, block_k, hkv, d), lambda b_, k_, _:
                             (b_, k_, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda b_, k_, _: (b_, 0, 0)),
            scratch_shapes=_scratch(h, d),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(lens, q, k_cache, v_cache)


def flash_decode_sliced(q, k_cache, v_cache, cache_len, *,
                        window: Optional[int] = None, block_k: int = BLOCK_K,
                        kv_slice: int = 1,
                        interpret: bool = False) -> SlicedOp:
    """Sliced, resumable flash decode: each slice merges ``kv_slice``
    cache blocks into the explicit (m, l, acc) carry — fp32 (B,H) /
    (B,H) / (B,H,D) — visiting blocks in the same order as
    :func:`flash_decode`, so the result is value-identical (pinned in
    tests/test_sliced_kernels.py)."""
    b, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    block_k = block_k_for(smax, block_k)
    n_kv = smax // block_k
    n_slices = n_slices_for(n_kv, kv_slice)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    scale = d ** -0.5

    def init():
        return (jnp.full((b, h), NEG_INF, jnp.float32),
                jnp.zeros((b, h), jnp.float32),
                jnp.zeros((b, h, d), jnp.float32))

    def step(carry, i):
        m0, l0, acc0 = carry
        k0 = i * kv_slice
        nk = min(kv_slice, n_kv - k0)
        ks = k_cache[:, k0 * block_k:(k0 + nk) * block_k]
        vs = v_cache[:, k0 * block_k:(k0 + nk) * block_k]
        kernel = functools.partial(
            _carry_kernel, scale=scale, window=window,
            kv_offset=k0 * block_k, block_k=block_k, n_kv=nk,
            n_heads=h, n_kv_heads=hkv)
        # (B, H) stats travel as (B, 1, H): a (1, H) block of a (B, H)
        # array breaks the TPU's 8-row tiling rule once B > 1
        carry_spec_1d = pl.BlockSpec((1, 1, h), lambda b_, k_, _: (b_, 0, 0))
        carry_spec_2d = pl.BlockSpec((1, h, d), lambda b_, k_, _: (b_, 0, 0))
        m, lsum, acc = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, nk),
                in_specs=[
                    pl.BlockSpec((1, h, d), lambda b_, k_, _: (b_, 0, 0)),
                    pl.BlockSpec((1, block_k, hkv, d), lambda b_, k_, _:
                                 (b_, k_, 0, 0)),
                    pl.BlockSpec((1, block_k, hkv, d), lambda b_, k_, _:
                                 (b_, k_, 0, 0)),
                    carry_spec_1d, carry_spec_1d, carry_spec_2d,
                ],
                out_specs=[carry_spec_1d, carry_spec_1d, carry_spec_2d],
                scratch_shapes=_scratch(h, d),
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
                jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
                jax.ShapeDtypeStruct((b, h, d), jnp.float32),
            ],
            interpret=interpret,
        )(lens, q, ks, vs, m0[:, None], l0[:, None], acc0)
        return m[:, 0], lsum[:, 0], acc

    def finalize(carry):
        _, lsum, acc = carry
        denom = jnp.maximum(lsum, 1e-30)
        return (acc / denom[..., None]).astype(q.dtype)

    return SlicedOp(n_slices, init, step, finalize, label="flash_decode")
