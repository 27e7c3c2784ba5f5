"""The configuration as the program runs it, and the seeded weights and
inputs, made on the device by the benchmark (never by the program)."""
from __future__ import annotations

import dataclasses

#: configuration file key -> the program's ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "norm": "norm",
          "rope_theta": "rope_theta"}

#: seed streams: one key per kind of input, all from ``--seed``
WEIGHTS, PROMPTS, FEED = 0, 1, 2


def program_config(sz: dict):
    """The program's ModelConfig for ``sz``: the registry entry named
    ``sz["model"]`` with the file's sizes.  A size that differs from the
    registry's and is not listed in ``reduced`` is an error."""
    from repro.configs import get
    cfg = get(sz["model"]).config()
    want = {f: sz[k] for k, f in FIELDS.items()}
    for k, f in FIELDS.items():
        if getattr(cfg, f) != want[f] and k not in sz["reduced"]:
            raise ValueError(f"{sz['model']}: {k}={sz[k]} in the file but "
                             f"{getattr(cfg, f)} in the program, and not "
                             f"listed in 'reduced'")
    return dataclasses.replace(cfg, **want)


def key(seed: int, stream: int):
    """A PRNG key for one stream of ``seed``; any seed up to 2**64."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def _init(sz: dict, k):
    """The weights in the program's layout: embedding (and a separate
    unembedding when untied), one stacked block per layer kind, norm
    weights where the norm has them.  Normal with scale 1/sqrt(fan in),
    the embedding at 0.02."""
    import jax
    import jax.numpy as jnp
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


def make_params(sz: dict, seed: int, device=None):
    """The weights of ``seed``, made on the device in one jitted call."""
    import functools

    import jax
    k = key(seed, WEIGHTS)
    if device is not None:
        k = jax.device_put(k, device)
    return jax.jit(functools.partial(_init, sz))(k)


def make_tokens(sz: dict, seed: int, stream: int, shapes: dict,
                device=None) -> dict:
    """Seeded token ids in [0, vocab): one int32 array per entry of
    ``shapes`` (name -> shape), made in one jitted call."""
    import jax
    import jax.numpy as jnp
    names = sorted(shapes)

    def gen(k):
        ks = jax.random.split(k, len(names))
        return {n: jax.random.randint(kk, shapes[n], 0, sz["vocab_size"],
                                      jnp.int32)
                for n, kk in zip(names, ks)}

    k = key(seed, stream)
    if device is not None:
        k = jax.device_put(k, device)
    return jax.jit(gen)(k)
