"""The configuration as the program runs it, and the seeded weights and
inputs, made on the device by the benchmark (never by the program).  What
depends on the block pattern, the file's keys and the weights' layout,
comes from the configuration's family (``families/<family>.py``)."""
from __future__ import annotations

import dataclasses

from . import catalog

#: seed streams: one key per kind of input, all from ``--seed``
WEIGHTS, PROMPTS, FEED = 0, 1, 2


def program_config(sz: dict):
    """The program's ModelConfig for ``sz``: the registry entry named
    ``sz["model"]`` with the file's sizes, the fields its family names.
    A size that differs from the registry's and is not listed in
    ``reduced`` is an error."""
    from repro.configs import get
    cfg = get(sz["model"]).config()
    fam = catalog.family(sz)
    fields = {k: (f, sz[k]) for k, f in fam.FIELDS.items()}
    if hasattr(fam, "program_fields"):
        fields.update(fam.program_fields(sz))
    for k, (f, want) in fields.items():
        if getattr(cfg, f) != want and k not in sz["reduced"]:
            raise ValueError(f"{sz['model']}: {k}={sz[k]} in the file but "
                             f"{getattr(cfg, f)} in the program, and not "
                             f"listed in 'reduced'")
    return dataclasses.replace(cfg, **{f: v for f, v in fields.values()})


def key(seed: int, stream: int):
    """A PRNG key for one stream of ``seed``; any seed up to 2**64."""
    import jax
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def make_params(sz: dict, seed: int, device=None):
    """The weights of ``seed`` in the program's layout, made by the
    configuration's family on the device in one jitted call."""
    import functools

    import jax
    k = key(seed, WEIGHTS)
    if device is not None:
        k = jax.device_put(k, device)
    return jax.jit(functools.partial(catalog.family(sz).init, sz))(k)


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
