"""The served kernels and smollm-135m's decode step compile for a v5e chip.

Nothing runs: each program is lowered from ``jax.eval_shape`` shapes placed
on a described (not attached) v5e device and compiled by the TPU compiler,
which refuses what interpret mode cannot see — block shapes off the
chip's tiling, too much VMEM, a program that does not fit.  The topology
is described inside a fixture, so only the worker that runs this file
loads the TPU library; where it cannot be described, every test skips.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels import ops
from repro.kernels.decode_attention import (cache_len_for, flash_decode,
                                            flash_decode_sliced)
from repro.kernels.flash_attention import flash_attention
from repro.models import transformer

# smollm-135m's serving shape in chip_smoke.py: batch 8, prompt 512, 128
# new tokens; 9 query heads over 3 kv heads of width 64
BATCH, PROMPT, DECODE = 8, 512, 128
HEADS, KV_HEADS, HEAD_DIM = 9, 3, 64
SMOLLM = (HEADS, KV_HEADS, HEAD_DIM)
#: olmo-1b's attention widths: 16 heads of 128, as many kv heads
OLMO = (16, 16, 128)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _decode_shapes(one_chip):
    smax = cache_len_for(PROMPT + DECODE)
    return _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, HEADS, HEAD_DIM), jnp.bfloat16),
        jax.ShapeDtypeStruct((BATCH, smax, KV_HEADS, HEAD_DIM),
                             jnp.bfloat16),
        jax.ShapeDtypeStruct((BATCH, smax, KV_HEADS, HEAD_DIM),
                             jnp.bfloat16),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32)))


def test_flash_decode_compiles_at_batch_8(one_chip):
    compiled = _compile(flash_decode, *_decode_shapes(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_sliced_compiles_at_batch_8(one_chip):
    def two_slices(q, k, v, lens):
        op = flash_decode_sliced(q, k, v, lens, kv_slice=1)
        return op.finalize(op.step(op.step(op.init(), 0), 1))

    compiled = _compile(two_slices, *_decode_shapes(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def _prompt_shapes(one_chip, prompt, widths=SMOLLM):
    heads, kv_heads, head_dim = widths
    q = jax.ShapeDtypeStruct((BATCH, prompt, heads, head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((BATCH, prompt, kv_heads, head_dim),
                              jnp.bfloat16)
    return _on(one_chip, (q, kv, kv))


def _loss_and_grads(q, k, v):
    return jax.value_and_grad(
        lambda *a: flash_attention(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


# the three RT prefills of the benchmark's mix, and olmo-1b's head width:
# the blocks these shapes take from attention_plan have to fit the chip's
# VMEM
@pytest.mark.parametrize("prompt,widths", [
    *(pytest.param(p, SMOLLM, id=str(p)) for p in (128, PROMPT, 1024)),
    *(pytest.param(p, OLMO, id=f"d128-{p}") for p in (PROMPT, 1024))])
def test_flash_attention_compiles_at_prompt_512(one_chip, prompt, widths):
    compiled = _compile(flash_attention,
                        *_prompt_shapes(one_chip, prompt, widths))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_and_its_vjp_compile_as_trained(one_chip):
    """The BE train step runs the forward kernel and its VJP at 8 x 512."""
    compiled = _compile(_loss_and_grads, *_prompt_shapes(one_chip, PROMPT))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("prompt", [PROMPT, 1024])
def test_flash_attention_and_its_vjp_compile_at_head_dim_128(one_chip,
                                                             prompt):
    compiled = _compile(_loss_and_grads,
                        *_prompt_shapes(one_chip, prompt, OLMO))
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_decode_step_compiles_at_published_widths(one_chip):
    cfg = get("smollm-135m").config()
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == \
        (HEADS, KV_HEADS, HEAD_DIM)
    params = _on(one_chip, transformer.param_specs(cfg))
    cache = _on(one_chip, jax.eval_shape(
        lambda: transformer.init_cache(cfg, BATCH,
                                       cache_len_for(PROMPT + DECODE))))
    tok = _on(one_chip, jax.ShapeDtypeStruct((BATCH,), jnp.int32))
    # the host is a CPU, so the dispatch would pick the references: steer
    # it to the compiled kernels the chip runs
    ops.set_use_pallas(True)
    try:
        compiled = _compile(
            lambda p, c, t, pos: transformer.decode_step(cfg, p, c, t, pos),
            params, cache, tok, tok)
    finally:
        ops.set_use_pallas(None)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2 ** 30
