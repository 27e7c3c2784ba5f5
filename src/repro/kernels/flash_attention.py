"""Flash attention forward kernel (Pallas, TPU target).

TPU-native adaptation of the flash algorithm (DESIGN.md §2): the grid is
(batch, heads, q_blocks, kv_blocks) with the kv dimension innermost — TPU
executes the grid sequentially, so the VMEM scratch accumulators (running
max / sum / output) persist across the kv blocks of one q block and the
output tile is flushed exactly once, at the last kv block.

Block shapes follow from the input's shape (:func:`attention_plan`): the
largest of 512, 256 and 128 positions that divides the sequence (or the
whole sequence: up to 128, or a multiple of 128 up to 512), shrunk until
the kernel's VMEM — the f32 score tiles, the (block_q, d) accumulator and
the double-buffered q/k/v/o tiles — fits ``VMEM_BUDGET``.  A caller that
passes ``block_q`` and ``block_k`` gets those.
Fewer, larger grid steps matter because each step pays a fixed pipeline
cost on top of its tile DMAs.

Causal and sliding-window masking is applied inside the kernel.  A kv
block that no row of the q block sees is neither computed (``pl.when``)
nor fetched: the kv index map clamps to the blocks the q block needs, so
the pipeline keeps the resident tile.  Skipping is value-identical — over
a hidden block p = 0 and the correction is 1, and ahead of the first
visible block the correction 0 erases what the block would have added —
so the sliced kernel, which walks every block, stays bit-identical at
equal blocks.  Both read KV heads through the index map (``h // group``),
not repeated.

Backward uses the XLA reference path via jax.custom_vjp (recompute-based,
matching the chunked reference); a Pallas backward kernel is a recorded
future optimization.

Validated against ref.attention_dense in interpret mode (tests/test_kernels).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.segments import SlicedOp, n_slices_for
from . import ref

NEG_INF = -1e30

#: block sizes tried, largest first; a sequence up to the first is one block
BLOCKS = (512, 256, 128)
#: VMEM the forward kernel's blocks may take, well under the v5e's 16 MiB
#: default scoped limit
VMEM_BUDGET = 8 * 2 ** 20


class AttentionPlan(NamedTuple):
    """How :func:`flash_attention` walks one (batch row, head): its block
    shapes, the grid steps in the walk and the steps it computes (those
    whose kv block some row of the q block sees)."""
    block_q: int
    block_k: int
    steps: int
    computed: int


def _blocks_for(s: int) -> list:
    """Candidate blocks for a sequence of ``s`` positions, largest first:
    the whole sequence when it is at most the smallest block, or a
    multiple of it up to the largest, then every block that divides it."""
    lane = BLOCKS[-1]
    whole = s <= lane or (s <= BLOCKS[0] and s % lane == 0)
    blocks = [s] if whole else []
    blocks += [b for b in BLOCKS if b < s and s % b == 0]
    if not blocks:
        raise ValueError(f"a sequence of {s} positions is no whole number "
                         f"of {lane}-position blocks")
    return blocks


def _vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """The forward kernel's VMEM: four f32 (block_q, block_k) tiles (scores,
    mask, probabilities and a temporary), the f32 accumulator and row
    stats, f32 copies of the kv tiles, and double-buffered q/k/v/o."""
    f32 = 4
    return (4 * block_q * block_k * f32 + block_q * (d + 2) * f32
            + 2 * block_k * d * f32
            + 2 * 2 * (block_q + block_k) * d * itemsize)


def _kv_span(qi, *, block_q: int, block_k: int, n_kv: int, causal: bool,
             window: Optional[int], q_offset: int):
    """First and last kv block that some row of q block ``qi`` sees — the
    one definition of what the mask hides, shared by the kernel's skip, its
    kv index map and :func:`attention_plan`.  ``qi`` is a grid index or an
    int."""
    lo, hi = (max, min) if isinstance(qi, int) else (jnp.maximum,
                                                     jnp.minimum)
    rows_lo = qi * block_q + q_offset
    last = n_kv - 1
    if causal:
        last = hi(last, (rows_lo + block_q - 1) // block_k)
    first = 0
    if window is not None:
        first = lo(rows_lo - window + 1, 0) // block_k
    return first, last


def attention_plan(sq: int, sk: int, d: int, *, causal: bool = True,
                   window: Optional[int] = None, q_offset: int = 0,
                   dtype=jnp.bfloat16, block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> AttentionPlan:
    """The blocks :func:`flash_attention` takes for q of ``sq`` and keys of
    ``sk`` positions at head size ``d``, and how many grid steps per
    (batch row, head) it walks and computes.  Without blocks they follow
    from the shape: the largest candidates (``BLOCKS``) that divide the
    sequences within ``VMEM_BUDGET``; given blocks (both or neither) are
    kept, capped at the sequence."""
    if (block_q is None) != (block_k is None):
        raise ValueError("give both blocks or neither")
    if block_q is None:
        itemsize = jnp.dtype(dtype).itemsize
        fits = [(bq, bk) for bq in _blocks_for(sq) for bk in _blocks_for(sk)
                if _vmem_bytes(bq, bk, d, itemsize) <= VMEM_BUDGET]
        if not fits:
            raise ValueError(f"no attention blocks for ({sq}, {sk}, {d}) "
                             f"fit {VMEM_BUDGET} bytes of VMEM")
        block_q, block_k = fits[0]
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) do not tile "
                         f"({sq}, {sk})")
    n_q, n_kv = sq // block_q, sk // block_k
    computed = 0
    for qi in range(n_q):
        first, last = _kv_span(qi, block_q=block_q, block_k=block_k,
                               n_kv=n_kv, causal=causal, window=window,
                               q_offset=q_offset)
        computed += max(last - first + 1, 0)
    return AttentionPlan(block_q, block_k, n_q * n_kv, computed)


def _block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  rows_base, cols_base, block_q: int, block_k: int):
    """One online-softmax block update on the VMEM carry scratch — the
    single definition shared by the whole-grid kernel and the sliced
    (resumable) kernel, so the two execute bit-identical math.  q·kᵀ takes
    q and k as stored (exact products, f32 accumulation); the softmax
    statistics, p and the accumulator are f32."""
    q = q_ref[0, 0]                               # (bq, d)
    k = k_ref[0, 0]                               # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = rows_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = cols_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: Optional[int],
                q_offset: int, block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    first, last = _kv_span(qi, block_q=block_q, block_k=block_k, n_kv=n_kv,
                           causal=causal, window=window, q_offset=q_offset)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((ki >= first) & (ki <= last))
    def _compute():
        _block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                      scale=scale, causal=causal, window=window,
                      rows_base=qi * block_q + q_offset,
                      cols_base=ki * block_k,
                      block_q=block_q, block_k=block_k)

    # skipped blocks leave the carry alone, so the walk's last step flushes
    @pl.when(ki == n_kv - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _fwd_carry_kernel(q_ref, k_ref, v_ref, m0_ref, l0_ref, acc0_ref,
                      m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool, window: Optional[int],
                      q_offset: int, kv_offset: int, block_q: int,
                      block_k: int, n_kv: int):
    """Resumable slice: same grid walk as ``_fwd_kernel`` over ``n_kv`` kv
    blocks starting at absolute column ``kv_offset``, but the softmax row
    stats + output accumulator enter as an explicit carry and leave as
    outputs instead of being normalized in place — the executor preempts
    between dispatches and a checkpoint can snapshot the carry."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = m0_ref[0, 0]
        l_scr[...] = l0_ref[0, 0]
        acc_scr[...] = acc0_ref[0, 0]

    _block_update(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                  scale=scale, causal=causal, window=window,
                  rows_base=qi * block_q + q_offset,
                  cols_base=kv_offset + ki * block_k,
                  block_q=block_q, block_k=block_k)

    @pl.when(ki == n_kv - 1)
    def _flush():
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        acc_ref[0, 0] = acc_scr[...]


def _fwd(q, k, v, *, causal, window, q_offset, block_q, block_k,
         interpret):
    """q (B, H, Sq, D); k, v (B, Hkv, Sk, D), H a multiple of Hkv."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    n_q, n_kv = sq // block_q, sk // block_k
    span = functools.partial(_kv_span, block_q=block_q, block_k=block_k,
                             n_kv=n_kv, causal=causal, window=window,
                             q_offset=q_offset)

    def kv_map(b_, h_, q_, k_):
        first, last = span(q_)
        return (b_, h_ // group, jnp.minimum(jnp.maximum(k_, first), last),
                0)

    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, q_, k_:
                         (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, q_, k_:
                               (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max
            pltpu.VMEM((block_q,), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    return _fwd(q, k, v, causal=causal, window=window, q_offset=q_offset,
                block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, q_offset, block_q, block_k,
               interpret):
    out = _flash(q, k, v, causal, window, q_offset, block_q, block_k,
                 interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, q_offset, block_q, block_k, interpret,
               res, g):
    q, k, v = res
    # recompute-based backward through the (chunked) reference — the
    # gradients of flash attention equal those of exact attention

    def f(q_, k_, v_):
        qt = jnp.moveaxis(q_, 1, 2)
        kt = jnp.moveaxis(k_, 1, 2)
        vt = jnp.moveaxis(v_, 1, 2)
        o = ref.attention(qt, kt, vt, causal=causal, window=window,
                          q_offset=q_offset)
        return jnp.moveaxis(o, 1, 2)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Public wrapper matching ref.attention's (B, S, H, D) convention.
    GQA reads each KV head through the kernel's index map.  Blocks not
    given come from :func:`attention_plan`."""
    sq, d = q.shape[1], q.shape[3]
    plan = attention_plan(sq, k.shape[1], d, causal=causal, window=window,
                          q_offset=q_offset, dtype=q.dtype, block_q=block_q,
                          block_k=block_k)
    qt = jnp.moveaxis(q, 1, 2)   # (B, H, S, D)
    kt = jnp.moveaxis(k, 1, 2)   # (B, Hkv, S, D)
    vt = jnp.moveaxis(v, 1, 2)
    o = _flash(qt, kt, vt, causal, window, q_offset, plan.block_q,
               plan.block_k, interpret)
    return jnp.moveaxis(o, 1, 2)


def flash_attention_sliced(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           kv_slice: int = 1,
                           interpret: bool = False) -> SlicedOp:
    """Sliced, resumable flash attention (DESIGN.md §6).

    Each slice dispatches ``kv_slice`` kv-block grid steps and threads the
    online-softmax carry (running max m, running sum l, unnormalized
    accumulator acc — fp32, (B,H,Sq)/(B,H,Sq)/(B,H,Sq,D)) explicitly, so
    the executor can preempt between slices with delay bounded by one
    slice.  Given the same blocks as the whole-grid kernel, it visits the
    kv blocks in the same order with the same math, so the result is
    value-identical to :func:`flash_attention` at equal blocks (pinned in
    tests/test_sliced_kernels.py); its 128-position default keeps slices
    short.  KV heads are read through the index map, as there.
    Forward-only: slicing exists for inference serving; training goes
    through :func:`flash_attention`."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    qt = jnp.moveaxis(q, 1, 2)   # (B, H, S, D)
    kt = jnp.moveaxis(k, 1, 2)   # (B, Hkv, S, D)
    vt = jnp.moveaxis(v, 1, 2)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    n_q, n_kv = sq // block_q, sk // block_k
    n_slices = n_slices_for(n_kv, kv_slice)
    scale = d ** -0.5

    def init():
        return (jnp.full((b, h, sq), NEG_INF, jnp.float32),
                jnp.zeros((b, h, sq), jnp.float32),
                jnp.zeros((b, h, sq, d), jnp.float32))

    def step(carry, i):
        m0, l0, acc0 = carry
        k0 = i * kv_slice
        nk = min(kv_slice, n_kv - k0)
        ks = kt[:, :, k0 * block_k:(k0 + nk) * block_k]
        vs = vt[:, :, k0 * block_k:(k0 + nk) * block_k]
        kernel = functools.partial(
            _fwd_carry_kernel, scale=scale, causal=causal, window=window,
            q_offset=q_offset, kv_offset=k0 * block_k, block_q=block_q,
            block_k=block_k, n_kv=nk)
        carry_spec_1d = pl.BlockSpec(
            (1, 1, block_q), lambda b_, h_, q_, k_: (b_, h_, q_))
        carry_spec_2d = pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0))
        return pl.pallas_call(
            kernel,
            grid=(b, h, n_q, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, q_, k_:
                             (b_, h_, q_, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, q_, k_:
                             (b_, h_ // group, k_, 0)),
                pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, q_, k_:
                             (b_, h_ // group, k_, 0)),
                carry_spec_1d, carry_spec_1d, carry_spec_2d,
            ],
            out_specs=[carry_spec_1d, carry_spec_1d, carry_spec_2d],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sq), jnp.float32),
                jax.ShapeDtypeStruct((b, h, sq), jnp.float32),
                jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            interpret=interpret,
        )(qt, ks, vs, m0, l0, acc0)

    def finalize(carry):
        _, lsum, acc = carry
        denom = jnp.maximum(lsum, 1e-30)
        o = (acc / denom[..., None]).astype(q.dtype)
        return jnp.moveaxis(o, 1, 2)

    return SlicedOp(n_slices, init, step, finalize,
                    label="flash_attention")
