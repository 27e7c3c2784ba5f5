"""Operations and bytes of the work the benchmark drives, from the
configuration's sizes alone (the keys of ``configs/<name>.json``).  Each
count is its family's (``families/<family>.py``); this module routes by
the configuration's family, so a metric reader works for every family.

A matmul of an (m, k) by a (k, n) is 2·m·k·n operations.  Counts are of
the work the result needs: causal attention counts the lower triangle,
a prefill counts the unembedding of the last position only (the one
whose logits are served), and training counts 6 operations per
parameter and token (forward and backward, recomputation excluded)."""
from __future__ import annotations

from . import catalog


def _fam(sz: dict):
    return catalog.family(sz)


def layer_matmul_params(sz: dict) -> int:
    """Weights of one layer's matmuls."""
    return _fam(sz).layer_matmul_params(sz)


def matmul_params(sz: dict) -> int:
    """Weights every token's forward pass multiplies: the layers and the
    unembedding (the embedding is a gather, not a matmul)."""
    return _fam(sz).matmul_params(sz)


def weight_bytes(sz: dict) -> int:
    """Bytes of the bf16 weights one forward pass reads."""
    return _fam(sz).weight_bytes(sz)


def attention_flops(sz: dict, q_len: int, kv_len: int, *,
                    causal: bool) -> float:
    """QK and PV of one sequence through every attention layer."""
    return _fam(sz).attention_flops(sz, q_len, kv_len, causal=causal)


def prefill_flops(sz: dict, batch: int, prompt: int) -> float:
    return _fam(sz).prefill_flops(sz, batch, prompt)


def kv_bytes_per_position(sz: dict) -> int:
    """K and V of one position through every attention layer, bf16."""
    return _fam(sz).kv_bytes_per_position(sz)


def prefill_bytes(sz: dict, batch: int, prompt: int) -> float:
    """Weights read once, the prompt's state written."""
    return _fam(sz).prefill_bytes(sz, batch, prompt)


def decode_step_flops(sz: dict, batch: int, ctx: int) -> float:
    """One token for each of ``batch`` rows whose caches hold ``ctx``
    positions (the new one included)."""
    return _fam(sz).decode_step_flops(sz, batch, ctx)


def decode_step_bytes(sz: dict, batch: int, ctx: int) -> float:
    """Weights read once, each row's state read up to its length, the new
    position's written."""
    return _fam(sz).decode_step_bytes(sz, batch, ctx)


def flash_decode_call(sz: dict, batch: int, ctx: int) -> tuple:
    """(operations, bytes) of one flash-decode kernel call: one attention
    layer of one decode step."""
    return _fam(sz).flash_decode_call(sz, batch, ctx)


def decode_attention_layers(sz: dict) -> int:
    """Layers that call the flash-decode kernel in one decode step."""
    return _fam(sz).decode_attention_layers(sz)


def train_step_flops(sz: dict, batch: int, seq: int) -> float:
    """One optimizer step's operations on ``batch`` rows of ``seq``."""
    return _fam(sz).train_step_flops(sz, batch, seq)


def least_time_s(ops: float, byts: float, peak: dict) -> float:
    """The larger of operations over peak bf16 rate and bytes over HBM
    bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"])
