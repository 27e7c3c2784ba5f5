"""The plain reference's shared pieces, in float32 jax.numpy at
``highest`` matmul precision: matmuls (and the fp8 control's rounding),
norms, RoPE, grouped-query causal attention, the SwiGLU MLP, the logits
and language-model loss of a family's trunk, and AdamW.  Each family
(``families/<family>.py``) builds its model from these; ``last_logits``
and ``lm_loss`` here call the configuration's family.  It imports nothing
of the program.

It reads weights in the layout the family's ``init`` makes them.
``quant`` = "fp8" computes every matmul on float8 (e4m3) operands, each
scaled by its largest magnitude: the control, one precision below the
configuration's bfloat16."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import catalog

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; gradients pass
    straight through the rounding."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant, spec="...k,kn->...n"):
    a, b = a.astype(F32), b.astype(F32)
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(sz, x, w):
    if sz["norm"] == "rms":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + sz["rms_norm_eps"])
        return x * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + sz["layer_norm_eps"])


def _rope(x, pos, theta):
    """Rotary embedding on (b, s, heads, hd): the two halves of each head
    are the real and imaginary parts."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(sz, quant, y, c):
    """Grouped-query causal self-attention of the normed ``y`` (b, s, d)
    through the weights ``c`` (wq, wk, wv, wo), its output projected
    back to d; RoPE at the configuration's ``rope_theta``, none where it
    states none."""
    b, s, d = y.shape
    h, kv = sz["num_attention_heads"], sz["num_key_value_heads"]
    hd = sz.get("head_dim") or d // h
    q = _mm(y, c["wq"], quant).reshape(b, s, h, hd)
    k = _mm(y, c["wk"], quant).reshape(b, s, kv, hd)
    v = _mm(y, c["wv"], quant).reshape(b, s, kv, hd)
    pos = jnp.arange(s)
    if sz.get("rope_theta"):
        q = _rope(q, pos, sz["rope_theta"])
        k = _rope(k, pos, sz["rope_theta"])
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = _mm(q, k, quant, "bqkgd,bskd->bkgqs") * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm(probs, v, quant, "bkgqs,bskd->bqkgd").reshape(b, s, h * hd)
    return _mm(o, c["wo"], quant)


def swiglu(quant, y, f):
    """The SwiGLU MLP of the normed ``y`` through ``f`` (wi, wg, wo)."""
    up = _mm(y, f["wi"], quant) * jax.nn.silu(_mm(y, f["wg"], quant))
    return _mm(up, f["wo"], quant)


def unembed(params):
    return params["unembed"] if "unembed" in params else params["embed"].T


def _items(sz: dict) -> tuple:
    """The configuration's scalar entries, hashable for a jit's static
    argument."""
    return tuple(sorted((k, v) for k, v in sz.items()
                        if not isinstance(v, (dict, list))))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 5))
def _last_logits(trunk, sz_items, quant, params, tokens, n_last):
    sz = dict(sz_items)
    x = trunk(sz, quant, params, tokens)[:, -n_last:]
    return _mm(x, unembed(params), quant)


def trunk_last_logits(trunk, sz: dict, params, tokens, n_last: int,
                      quant=None, rows: int = 2):
    """Logits (b, n_last, vocab) of the last ``n_last`` positions of each
    row of ``tokens`` (b, s), ``rows`` rows at a time so that a long
    sequence fits.  ``trunk(sz, quant, params, tokens)`` gives the final
    normed hidden states; the unembedding (or the tied embedding)
    follows."""
    out = [_last_logits(trunk, _items(sz), quant, params,
                        tokens[i:i + rows], n_last)
           for i in range(0, tokens.shape[0], rows)]
    return jnp.concatenate(out)


def trunk_lm_loss(trunk, sz: dict, params, inputs, labels, z_loss: float,
                  quant=None):
    """Mean next-token cross-entropy plus ``z_loss`` times the squared
    log-partition, over every position, of ``trunk``'s logits."""
    x = trunk(sz, quant, params, inputs)
    logits = _mm(x, unembed(params), quant)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold + z_loss * logz ** 2)


def last_logits(sz: dict, params, tokens, n_last: int, quant=None,
                rows: int = 2):
    """The configuration's family's logits of the last ``n_last``
    positions of each row of ``tokens``."""
    return catalog.family(sz).last_logits(sz, params, tokens, n_last,
                                          quant, rows)


def lm_loss(sz: dict, params, inputs, labels, z_loss: float, quant=None):
    """The configuration's family's language-model loss."""
    return catalog.family(sz).lm_loss(sz, params, inputs, labels, z_loss,
                                      quant)


def lr_at(opt: dict, step):
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1 - r) * cos)


def _train_step(sz_items, opt_items, quant, params, m, v, step, inputs,
                labels):
    sz, opt = dict(sz_items), dict(opt_items)
    loss, g = jax.value_and_grad(
        lambda p: lm_loss(sz, p, inputs, labels, opt["z_loss"], quant))(
        params)
    g = jax.tree.map(lambda t: t.astype(F32), g)
    gnorm = jnp.sqrt(sum(jnp.sum(t * t) for t in jax.tree.leaves(g)))
    g = jax.tree.map(lambda t: t * jnp.minimum(
        1.0, opt["grad_clip"] / (gnorm + 1e-9)), g)
    step = step + 1
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, t: b1 * a + (1 - b1) * t, m, g)
    v = jax.tree.map(lambda a, t: b2 * a + (1 - b2) * t * t, v, g)

    def upd(p, mm, vv):
        delta = (mm / (1 - b1 ** step)) / (
            jnp.sqrt(vv / (1 - b2 ** step)) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * delta).astype(p.dtype)

    norms = jax.tree.map(lambda t: jnp.sqrt(jnp.sum(t * t)), g)
    return loss, norms, jax.tree.map(upd, params, m, v), m, v, step


_train_step_jit = jax.jit(_train_step, static_argnums=(0, 1, 2),
                          donate_argnums=(3, 4, 5))


def train(sz: dict, opt: dict, params, batches, quant=None):
    """AdamW from ``params`` over ``batches`` (a list of (inputs, labels)).
    Returns (losses, per-leaf norms of the first clipped gradient, the
    final params).  Gradients are taken with respect to the bf16
    weights, the arithmetic in float32; the weights are stored back in
    their own type after each step.  Weight decay applies to every leaf
    of two or more dimensions, as the configuration's optimizer states
    it (a layer-stacked norm gain is such a leaf)."""
    items = _items(sz)
    opt_items = tuple(sorted(opt.items()))
    m = jax.tree.map(lambda t: jnp.zeros(t.shape, F32), params)
    v = jax.tree.map(lambda t: jnp.zeros(t.shape, F32), params)
    step = jnp.zeros((), jnp.int32)
    losses, first = [], None
    for inputs, labels in batches:
        loss, g, params, m, v, step = _train_step_jit(
            items, opt_items, quant, params, m, v, step, inputs, labels)
        losses.append(float(loss))
        if first is None:
            flat = jax.tree_util.tree_flatten_with_path(g)[0]
            first = {jax.tree_util.keystr(p): float(x) for p, x in flat}
    return losses, first, params


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda t: jnp.sqrt(jnp.sum(jnp.square(
        t.astype(F32)))), tree)


def leaf_norms(tree) -> dict:
    """{leaf path: L2 norm} of a pytree, as host floats."""
    flat = jax.tree_util.tree_flatten_with_path(_norms(tree))[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))), a, b)


def change_norms(new, old) -> dict:
    """{leaf path: L2 norm of new - old}."""
    flat = jax.tree_util.tree_flatten_with_path(_diff_norms(new, old))[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}
