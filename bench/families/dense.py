"""The dense decoder family: pre-norm layers of grouped-query causal
attention (RoPE) and a SwiGLU MLP, all of one kind, stacked as one
pattern position.  A configuration without a ``"family"`` key is this.

A family module gives the benchmark what depends on a configuration's
block pattern:

- ``FIELDS``: configuration file key -> the program's ``ModelConfig``
  field, compared with the registry entry by ``model.program_config``;
  optionally ``program_fields(sz)``, {file key: (field, value)} for a
  value the program holds in another form (a pattern as ``BlockSpec``s);
- ``init(sz, key)``: the seeded weights in the program's layout, which
  ``model.make_params`` jits;
- the plain float32 reference, ``last_logits`` and ``lm_loss``, built
  from ``bench/reference.py``'s pieces and importing nothing of the
  program;
- the operation and byte counts that ``bench/flops.py`` routes here."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import F32, _norm

#: configuration file key -> the program's ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "norm": "norm",
          "rope_theta": "rope_theta"}


# -- weights --------------------------------------------------------------

def init(sz: dict, k):
    """The weights in the program's layout: embedding (and a separate
    unembedding when untied), one stacked block per layer kind, norm
    weights where the norm has them.  Normal with scale 1/sqrt(fan in),
    the embedding at 0.02."""
    d, h, kv = sz["hidden_size"], sz["num_attention_heads"], \
        sz["num_key_value_heads"]
    hd = sz["head_dim"]
    f, v, n_l = sz["intermediate_size"], sz["vocab_size"], \
        sz["num_hidden_layers"]
    rms = sz["norm"] == "rms"
    keys = iter(jax.random.split(k, 16))

    def w(shape, fan_in):
        x = jax.random.normal(next(keys), shape, jnp.float32)
        return (x * fan_in ** -0.5).astype(jnp.bfloat16)

    core = {"wq": w((n_l, d, h * hd), d), "wk": w((n_l, d, kv * hd), d),
            "wv": w((n_l, d, kv * hd), d), "wo": w((n_l, h * hd, d), d)}
    ffn = {"wi": w((n_l, d, f), d), "wg": w((n_l, d, f), d),
           "wo": w((n_l, f, d), f)}
    if rms:
        core["norm_w"] = jnp.ones((n_l, d), jnp.float32)
        ffn["norm_w"] = jnp.ones((n_l, d), jnp.float32)
    params = {"embed": (jax.random.normal(next(keys), (v, d), jnp.float32)
                        * 0.02).astype(jnp.bfloat16),
              "blocks": [{"core": core, "ffn": ffn}]}
    if not sz["tie_word_embeddings"]:
        params["unembed"] = w((d, v), d)
    if rms:
        params["final_norm_w"] = jnp.ones((d,), jnp.float32)
    return params


# -- the plain reference --------------------------------------------------

def layer(sz, quant, x, p):
    """One pre-norm layer: attention, then the SwiGLU MLP, each added to
    the residual."""
    c, f = p["core"], p["ffn"]
    x = x + reference.attention(sz, quant, _norm(sz, x, c.get("norm_w")), c)
    return x + reference.swiglu(quant, _norm(sz, x, f.get("norm_w")), f)


def trunk(sz, quant, params, tokens):
    """Embedding, every layer (a scan over the stacked block), final
    norm: (b, s) tokens -> (b, s, d) float32."""
    x = params["embed"][tokens].astype(F32)
    one = jax.checkpoint(functools.partial(layer, sz, quant))
    x, _ = jax.lax.scan(lambda x, p: (one(x, p), None), x,
                        params["blocks"][0])
    return _norm(sz, x, params.get("final_norm_w"))


def last_logits(sz: dict, params, tokens, n_last: int, quant=None,
                rows: int = 2):
    return reference.trunk_last_logits(trunk, sz, params, tokens, n_last,
                                       quant, rows)


def lm_loss(sz: dict, params, inputs, labels, z_loss: float, quant=None):
    return reference.trunk_lm_loss(trunk, sz, params, inputs, labels,
                                   z_loss, quant)


# -- operations and bytes (see bench/flops.py) ------------------------------

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


def decode_attention_layers(sz: dict) -> int:
    """Layers that call the flash-decode kernel in a decode step: all."""
    return sz["num_hidden_layers"]


def train_step_flops(sz: dict, batch: int, seq: int) -> float:
    """6·N·tokens plus three times the forward attention (the formula of
    the repository's roofline model)."""
    return (6.0 * matmul_params(sz) * batch * seq
            + 3.0 * batch * attention_flops(sz, seq, seq, causal=True))
