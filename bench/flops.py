"""Operations and bytes of the work the benchmark drives, from the
configuration's sizes alone (the keys of ``configs/<name>.json``).

A matmul of an (m, k) by a (k, n) is 2·m·k·n operations.  Counts are of
the work the result needs: causal attention counts the lower triangle,
a prefill counts the unembedding of the last position only (the one
whose logits are served), and training counts 6 operations per
parameter and token (forward and backward, recomputation excluded)."""
from __future__ import annotations

BF16 = 2


def _dims(sz: dict):
    d, h, kv = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"]
    hd = sz["head_dim"]
    return d, h, kv, hd, sz["intermediate_size"], sz["vocab_size"], \
        sz["num_hidden_layers"]


def layer_matmul_params(sz: dict) -> int:
    """Weights of one layer's matmuls: q, k, v, o and a SwiGLU MLP."""
    d, h, kv, hd, f, _, _ = _dims(sz)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(sz: dict) -> int:
    """Weights every token's forward pass multiplies: the layers and the
    unembedding (the embedding is a gather, not a matmul)."""
    d, _, _, _, _, v, n_l = _dims(sz)
    return n_l * layer_matmul_params(sz) + v * d


def weight_bytes(sz: dict) -> int:
    """Bytes of the bf16 weights one forward pass reads: the layers, the
    unembedding and, for a separate embedding table, nothing more (a
    gather reads a row per token)."""
    return matmul_params(sz) * BF16


def attention_flops(sz: dict, q_len: int, kv_len: int, *,
                    causal: bool) -> float:
    """QK and PV of one sequence through every layer: 4·H·hd per
    (query, key) pair, the lower triangle only when causal."""
    _, h, _, hd, _, _, n_l = _dims(sz)
    pairs = q_len * (q_len + 1) / 2 if causal else q_len * kv_len
    return 4.0 * h * hd * pairs * n_l


def prefill_flops(sz: dict, batch: int, prompt: int) -> float:
    d, _, _, _, _, v, n_l = _dims(sz)
    per_tok = 2.0 * n_l * layer_matmul_params(sz)
    return (batch * prompt * per_tok + 2.0 * batch * d * v
            + batch * attention_flops(sz, prompt, prompt, causal=True))


def kv_bytes_per_position(sz: dict) -> int:
    """K and V of one position through every layer, bf16."""
    _, _, kv, hd, _, _, n_l = _dims(sz)
    return 2 * n_l * kv * hd * BF16


def prefill_bytes(sz: dict, batch: int, prompt: int) -> float:
    """Weights read once, the prompt's K and V written."""
    return weight_bytes(sz) + batch * prompt * kv_bytes_per_position(sz)


def decode_step_flops(sz: dict, batch: int, ctx: int) -> float:
    """One token for each of ``batch`` rows whose caches hold ``ctx``
    positions (the new one included)."""
    _, h, _, hd, _, _, n_l = _dims(sz)
    return batch * (2.0 * matmul_params(sz) + 4.0 * h * hd * ctx * n_l)


def decode_step_bytes(sz: dict, batch: int, ctx: int) -> float:
    """Weights read once, each row's K and V read up to its length, the
    new position's K and V written."""
    kvp = kv_bytes_per_position(sz)
    return weight_bytes(sz) + batch * ctx * kvp + batch * kvp


def flash_decode_call(sz: dict, batch: int, ctx: int) -> tuple:
    """(operations, bytes) of one flash-decode kernel call: one layer of
    one decode step.  It reads q, each row's K and V up to ``ctx``, and
    writes the output."""
    _, h, kv, hd, _, _, _ = _dims(sz)
    ops = 4.0 * batch * h * hd * ctx
    byts = (2 * batch * ctx * kv * hd + 2 * batch * h * hd) * BF16
    return ops, byts


def train_step_flops(sz: dict, batch: int, seq: int) -> float:
    """6·N·tokens plus three times the forward attention (the formula of
    the repository's roofline model)."""
    return (6.0 * matmul_params(sz) * batch * seq
            + 3.0 * batch * attention_flops(sz, seq, seq, causal=True))


def least_time_s(ops: float, byts: float, peak: dict) -> float:
    """The larger of operations over peak bf16 rate and bytes over HBM
    bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"])
