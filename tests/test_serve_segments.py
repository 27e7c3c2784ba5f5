"""Serving engine as GPU-access segments: sliced decode is value-identical
to inline decode, engine state commits only at finalize (an abandoned
carry is harmless), and the segment dispatches through the preemptive
executor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.launch.serve import InferenceEngine
from repro.sched import DeviceExecutor, RTJob


@pytest.fixture(scope="module")
def engine():
    cfg = get("smollm-135m").reduced()
    eng = InferenceEngine(cfg, max_len=48)
    return eng


def _prefill(engine, seed=0):
    prompt = jax.random.randint(jax.random.PRNGKey(seed), (2, 8), 0,
                                engine.cfg.vocab_size)
    engine.prefill_batch(prompt)


@pytest.mark.parametrize("slice_tokens", [1, 2, 3])
def test_decode_segment_matches_inline_decode(engine, slice_tokens):
    n = 6
    _prefill(engine)
    want = engine.decode_chunk(n)          # inline (slice_tokens=1) path
    _prefill(engine)                       # reset engine state
    op = engine.decode_segment(n, slice_tokens=slice_tokens)
    assert op.n_slices == -(-n // slice_tokens)
    got = op.run()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_segment_commits_state_only_at_finalize(engine):
    _prefill(engine)
    pos_before = int(engine.pos if jnp.ndim(engine.pos) == 0
                     else engine.pos[0])
    op = engine.decode_segment(4)
    carry = op.init()
    carry = op.step(carry, 0)
    carry = op.step(carry, 1)
    # engine untouched while the carry is in flight (a preempted or
    # abandoned op must not corrupt the serving state)
    pos_mid = int(engine.pos if jnp.ndim(engine.pos) == 0
                  else engine.pos[0])
    assert pos_mid == pos_before
    for i in range(2, op.n_slices):
        carry = op.step(carry, i)
    toks = op.finalize(carry)
    pos_after = int(engine.pos if jnp.ndim(engine.pos) == 0
                    else engine.pos[0])
    assert pos_after == pos_before + 4
    assert toks.shape == (2, 4)


def test_decode_segment_under_executor(engine):
    _prefill(engine)
    want = engine.decode_chunk(5)
    _prefill(engine)
    ex = DeviceExecutor(policy="notify", wait_mode="suspend")
    got = []

    def body(job, it):
        with ex.device_segment(job):
            got.append(ex.run_sliced(job, engine.decode_segment(5)))

    job = RTJob("decode", body, period_s=10.0, priority=10)
    job.start(ex)
    job.join(60)
    ex.shutdown()
    assert got, "decode job did not complete"
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
    # one timing sample per token slice + one for finalize
    assert len(job.stats.slice_times) == 6
    assert job.stats.mort is not None


def test_engine_serves_lengths_off_the_decode_block():
    """512 + 128 is no whole number of 512-token decode blocks: the
    kernel tiles the 640-position cache with 128-position blocks instead
    of the engine padding it, and the compiled kernel path (interpret mode
    here) scores the generation like the reference path does."""
    from repro.kernels import ops
    from repro.launch.serve import random_prompts

    cfg = get("smollm-135m").reduced()
    prompt = random_prompts(cfg, 2, 512)
    ops.set_use_pallas(True, interpret=True)
    try:
        eng = InferenceEngine(cfg, max_len=512 + 128)
        assert eng.max_len == 640
        eng.prefill_batch(prompt)
        toks = eng.decode_segment(128, slice_tokens=32).run()
        assert toks.shape == (2, 128)
        inputs = jnp.concatenate([prompt[:, -1:], toks[:, :-1]], axis=1)
        got = eng.teacher_forced_logits(prompt, inputs)
    finally:
        ops.set_use_pallas(None)
    ops.set_use_pallas(False)
    try:
        want = InferenceEngine(cfg, params=eng.params, max_len=640) \
            .teacher_forced_logits(prompt, inputs)
    finally:
        ops.set_use_pallas(None)
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        # bf16 through 4 layers: the kernels and references round apart
        assert np.linalg.norm(g - w) <= 0.05 * np.linalg.norm(w)
