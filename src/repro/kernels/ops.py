"""Public jit-ready wrappers for the kernel layer.

Every perf-critical op in the model stack goes through this module, which
dispatches between the Pallas TPU kernels (``use_pallas=True``, the target
runtime) and the pure-jnp references in ``ref.py`` (CPU dry-run, smoke
tests, and the oracle for kernel validation).

The global default is platform-derived: Pallas on TPU, reference elsewhere.
``set_use_pallas`` overrides it (tests use interpret-mode Pallas on CPU);
the ``REPRO_PALLAS`` environment variable provides the same override for
subprocesses (``interpret`` → interpret-mode Pallas, as in CI's
runtime-smoke job; ``off`` → the references).  Both are CPU-test
switches: on a TPU backend they would hide the compiled kernels, so
there they raise instead of taking effect.

The ``*_sliced`` entry points return a :class:`repro.core.segments.
SlicedOp` — the op split into K-grid-step dispatches with an explicit
carry — so the real-time executor can preempt between slices (bounded
preemption delay, DESIGN.md §6) and checkpoint mid-op.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax

from ..core.segments import SlicedOp
from . import ref

#: (use_pallas, interpret) set by ``set_use_pallas``; None -> environment
_OVERRIDE: Optional[Tuple[Optional[bool], bool]] = None

#: REPRO_PALLAS value -> (use_pallas, interpret); None -> platform default
_ENV_DISPATCH = {"": (None, False), "interpret": (True, True),
                 "off": (False, False)}


def set_use_pallas(value: Optional[bool], interpret: bool = False) -> None:
    """Override the dispatch for this process (None restores the
    environment / platform default)."""
    global _OVERRIDE
    _OVERRIDE = None if value is None else (value, interpret)


def _dispatch() -> Tuple[Optional[bool], bool]:
    if _OVERRIDE is not None:
        return _OVERRIDE
    env = os.environ.get("REPRO_PALLAS", "").lower()
    if env not in _ENV_DISPATCH:
        raise ValueError(f"REPRO_PALLAS={env!r}: expected one of "
                         f"{sorted(k for k in _ENV_DISPATCH if k)}")
    value, interpret = _ENV_DISPATCH[env]
    if (value is False or interpret) and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"REPRO_PALLAS={env} would keep the compiled Pallas kernels "
            f"off the TPU; it is a CPU-test switch — unset it")
    return value, interpret


def use_pallas() -> bool:
    value = _dispatch()[0]
    if value is not None:
        return value
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    return _dispatch()[1]


def _sliced_interpret() -> bool:
    """Sliced execution always goes through the Pallas kernels (the carry
    contract is kernel-level); off-TPU they run in interpret mode."""
    if use_pallas():
        return interpret_mode()
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              q_offset: int = 0, chunk: int = 2048):
    """Multi-head (GQA) attention: q (B,Sq,H,D), k/v (B,Sk,Hkv,D)."""
    if use_pallas():
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, interpret=interpret_mode())
    return ref.attention(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, chunk=chunk)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None):
    """Single-token attention over a KV cache: q (B,H,D)."""
    if use_pallas():
        from .decode_attention import flash_decode
        return flash_decode(q, k_cache, v_cache, cache_len, window=window,
                            interpret=interpret_mode())
    return ref.decode_attention(q, k_cache, v_cache, cache_len,
                                window=window)


# --------------------------------------------------------------------------
# recurrences
# --------------------------------------------------------------------------

def mamba_scan(x, dt, A, B, C, D, h0=None):
    if use_pallas():
        from .mamba_scan import mamba_scan_pallas
        return mamba_scan_pallas(x, dt, A, B, C, D, h0=h0,
                                 interpret=interpret_mode())
    return ref.mamba_scan(x, dt, A, B, C, D, h0=h0)


def rwkv6_scan(r, k, v, w, u, s0=None):
    if use_pallas():
        from .rwkv6 import rwkv6_scan_pallas
        return rwkv6_scan_pallas(r, k, v, w, u, s0=s0,
                                 interpret=interpret_mode())
    return ref.rwkv6_scan(r, k, v, w, u, s0=s0)


def mamba_decode_step(x, dt, A, B, C, D, h):
    return ref.mamba_decode_step(x, dt, A, B, C, D, h)


def rwkv6_decode_step(r, k, v, w, u, state):
    return ref.rwkv6_decode_step(r, k, v, w, u, state)


# --------------------------------------------------------------------------
# sliced, resumable entry points (bounded preemption delay — DESIGN.md §6)
# --------------------------------------------------------------------------

def attention_sliced(q, k, v, *, causal: bool = True,
                     window: Optional[int] = None, q_offset: int = 0,
                     block_q: int = 128, block_k: int = 128,
                     kv_slice: int = 1) -> SlicedOp:
    """Flash attention as a SlicedOp: ``kv_slice`` kv-block grid steps per
    dispatch, explicit (m, l, acc) carry between dispatches.  Value-
    identical to :func:`attention` on the Pallas path at equal blocks."""
    from .flash_attention import flash_attention_sliced
    return flash_attention_sliced(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_slice=kv_slice,
        interpret=_sliced_interpret())


def decode_attention_sliced(q, k_cache, v_cache, cache_len, *,
                            window: Optional[int] = None,
                            block_k: int = 512,
                            kv_slice: int = 1) -> SlicedOp:
    """Flash decode as a SlicedOp over cache blocks (carry: m, l, acc)."""
    from .decode_attention import flash_decode_sliced
    return flash_decode_sliced(
        q, k_cache, v_cache, cache_len, window=window, block_k=block_k,
        kv_slice=kv_slice, interpret=_sliced_interpret())


def mamba_scan_sliced(x, dt, A, B, C, D, h0=None, *, chunk: int = 32,
                      block_d: int = 512,
                      slice_chunks: int = 1) -> SlicedOp:
    """Selective scan as a SlicedOp over time windows (carry: recurrent h
    + output buffer).  Each window dispatches through the normal
    pallas/reference dispatch, so this works on both paths."""
    from .mamba_scan import mamba_scan_sliced as _sliced
    if use_pallas():
        return _sliced(x, dt, A, B, C, D, h0=h0, chunk=chunk,
                       block_d=block_d, slice_chunks=slice_chunks,
                       interpret=interpret_mode())
    return _sliced(x, dt, A, B, C, D, h0=h0, chunk=chunk, block_d=block_d,
                   slice_chunks=slice_chunks,
                   scan_fn=lambda xw, dtw, A_, Bw, Cw, D_, h:
                   ref.mamba_scan(xw, dtw, A_, Bw, Cw, D_, h0=h))


def rwkv6_scan_sliced(r, k, v, w, u, s0=None, *, chunk: int = 32,
                      slice_chunks: int = 1) -> SlicedOp:
    """WKV recurrence as a SlicedOp over time windows (carry: (B,H,D,D)
    state + output buffer); pallas/reference dispatch per window."""
    from .rwkv6 import rwkv6_scan_sliced as _sliced
    if use_pallas():
        return _sliced(r, k, v, w, u, s0=s0, chunk=chunk,
                       slice_chunks=slice_chunks, interpret=interpret_mode())
    return _sliced(r, k, v, w, u, s0=s0, chunk=chunk,
                   slice_chunks=slice_chunks,
                   scan_fn=lambda rw, kw, vw, ww, u_, st:
                   ref.rwkv6_scan(rw, kw, vw, ww, u_, s0=st))
