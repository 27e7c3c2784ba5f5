"""Entry-point set-up: the persistent compile cache lands where it should,
too few visible devices is an error rather than a silent fallback, a
failed job fails the preemptive-serving check, and a registered serving
workload builds its engine on the device its job is bound to."""
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from repro import runtime
from repro.core.segments import SegmentedWorkload, SlicedOp
from repro.launch.fleet import default_fleet, launch_fleet
from repro.launch.preemptive import (HOST_PAUSE_MS, MARGIN, PROFILE_REPS,
                                     check_preemptive, epsilon_ms,
                                     run_preemptive)

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch, tmp_path,
                                                     cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache() == path     # fixed, not per call


def test_visible_devices_refuses_more_than_visible():
    n = len(jax.devices())
    assert runtime.visible_devices(n) == jax.devices()
    with pytest.raises(SystemExit, match=f"{n + 1} device"):
        runtime.visible_devices(n + 1)


def test_launch_fleet_refuses_more_devices_than_visible():
    n = len(jax.devices()) + 1
    with pytest.raises(SystemExit, match="visible"):
        launch_fleet(default_fleet(n, ["chat"]), n_devices=n,
                     verbose=False)


def _host_op(label, n_slices=2, slice_s=0.002, fail_after=None):
    """A host-timed sliced segment; with ``fail_after`` its step raises
    once it has been built that many times (after profiling)."""
    built = [0]

    def factory():
        built[0] += 1
        doomed = fail_after is not None and built[0] > fail_after

        def step(carry, i):
            if doomed:
                raise ValueError(f"{label} body failed")
            time.sleep(slice_s)
            return carry + 1

        return SlicedOp(n_slices, lambda: 0, step, lambda c: c, label=label)

    return factory


def test_failed_job_fails_the_preemptive_check():
    infer = SegmentedWorkload("infer").device(_host_op("decode"))
    # the profile builds the op PROFILE_REPS times; the scheduled run
    # then fails
    train = SegmentedWorkload("train").device(
        _host_op("train", fail_after=PROFILE_REPS))
    result = run_preemptive(infer, train)
    assert set(result["errors"]) == {"train"}
    with pytest.raises(RuntimeError, match="'train' failed"):
        check_preemptive(result)


def test_preemptive_check_passes_a_clean_run():
    # slices long enough that thread wake-up jitter on a loaded host
    # (tens of ms) stays inside the margin admission adds to the measured
    # profile and to the longest slice that ε is made of
    infer = SegmentedWorkload("infer").device(_host_op("decode",
                                                       slice_s=0.1))
    train = SegmentedWorkload("train").device(_host_op("train",
                                                       slice_s=0.1))
    result = run_preemptive(infer, train)
    check_preemptive(result)
    assert result["rt_mort_ms"] <= result["wcrt_ms"]
    assert result["epsilon_ms"] == epsilon_ms(result["profiled_max_slice_ms"])


def test_epsilon_covers_a_slice_a_host_pause_stretches():
    # a pause stops every thread for its whole length, so a slice it meets
    # lasts up to the slice and the pause, however short the slice
    for slice_ms in (0.5, 64.0, 160.0):
        eps = epsilon_ms(slice_ms)
        assert eps >= slice_ms + HOST_PAUSE_MS
        assert eps == pytest.approx(1.0 + MARGIN * slice_ms + HOST_PAUSE_MS)


_PLACEMENT = r"""
import jax
from repro.configs import get
from repro.launch.serve import register_serving_workloads
from repro.sched import JobProfile, connect

engines = register_serving_workloads(get("smollm-135m").reduced())
client = connect(n_devices=2, policy="ioctl", wait_mode="suspend")
res = client.submit(
    JobProfile("pinned", [1.0], [(1.0, 1.0)], period_ms=60000.0,
               priority=10, device=1),
    workload_spec={"name": "serve.decode",
                   "kwargs": {"batch": 2, "prompt_len": 8, "decode": 4}},
    n_iterations=1, start=True)
assert res.accepted, res
client.join(300)
client.close(shutdown=True)
assert res.job.error is None, res.job.error
eng = engines[(1, 2, 8, 4)]
where = {d for x in jax.tree.leaves((eng.params, eng.cache))
         for d in x.devices()}
assert where == {jax.devices()[1]}, where
assert eng.last_out.shape == (2, 4)
print("PLACED OK")
"""


def test_serving_workload_engine_lives_on_bound_device():
    """Run with two virtual devices in a subprocess so the device-count
    flag does not leak into this process."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _PLACEMENT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=str(ROOT))
    assert "PLACED OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
