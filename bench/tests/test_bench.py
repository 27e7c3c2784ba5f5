"""Checks of the benchmark itself, on the CPU with no chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests

The trace reduction on a small trace recorded on a v5e, the operation
and byte counts against numbers worked out by hand, the dense family's
weights, reference and counts against values recorded before it moved
into ``families/dense.py``, the response-from-due arithmetic, discovery
of new data files by name, the refusals of ``bench/run.py``, and whole
runs of a tiny cell with the timed path broken underneath, each of which
has to come out not correct, and of a cell whose family and BE
configuration come as new files."""
from __future__ import annotations

import hashlib
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench import catalog, flops, traffic  # noqa: E402
from bench import trace as tr  # noqa: E402

SMOLLM = catalog.config("smollm-135m")
#: smollm-135m cut to 2 layers of width 72 with a 128-token vocabulary
TINY = dict(SMOLLM, num_hidden_layers=2, hidden_size=72, head_dim=8,
            intermediate_size=192, vocab_size=128)
TINY_REDUCED = ["num_hidden_layers", "hidden_size", "head_dim",
                "intermediate_size", "vocab_size", "rms_norm_eps"]
#: OLMo-1B's widths (arXiv:2402.00838) at 8 of its 16 layers
OLMO = {"num_hidden_layers": 8, "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 8192, "vocab_size": 50304}


# -- the trace reduction ----------------------------------------------------

def test_reduce_recorded_trace():
    """A slice of a traced run on one v5e: busy time is the union of the
    device's operations inside ``bench.window``, every idle gap is named
    by the host span over it, and operation times add up."""
    rec = json.loads((CHECKOUT / "bench" / "testdata" /
                      "trace_small.json").read_text())
    out = tr.reduce(rec["trace"])
    for k, v in rec["expected"].items():
        assert out[k] == pytest.approx(v, rel=1e-9), k
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert sum(out["op_time"].values()) >= out["busy_s"] - 1e-12
    names = {n for n, _ in out["idle_gaps"]}
    assert names <= {"none", "bench.rt_prefill", "bench.rt_decode_slice",
                     "bench.be_train_slice", "bench.admit"}


def test_busy_is_the_union_of_overlapping_operations():
    """Two overlapping operations on one device and one on another: busy
    time is the union per device, averaged over devices; gaps are named
    by the host span covering most of them."""
    ms = 1_000_000
    t = {"devices": {"/device:TPU:0": [["a", 0, 4 * ms], ["b", 2 * ms,
                                                           4 * ms]],
                     "/device:TPU:1": [["a", 8 * ms, 2 * ms]]},
         "host": [["bench.window", 0, 10 * ms, {}],
                  ["bench.rt_decode_slice", 6 * ms, 4 * ms, {}]]}
    out = tr.reduce(t)
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx((0.006 + 0.002) / 2)
    assert out["idle_share"] == pytest.approx(0.6)
    assert out["op_time"] == {"a": pytest.approx(0.006),
                              "b": pytest.approx(0.004)}
    gaps = dict((round(s, 9), n) for n, s in out["idle_gaps"])
    assert gaps[0.008] == "none"            # device 1, 0-8 ms
    assert gaps[0.004] == "bench.rt_decode_slice"   # device 0, 6-10 ms


# -- operations and bytes ---------------------------------------------------

def test_matmul_params_by_hand():
    # smollm-135m: 30 × (576·576·2 + 2·576·192 + 3·576·1536) + 49152·576
    assert flops.layer_matmul_params(SMOLLM) == 3_538_944
    assert flops.matmul_params(SMOLLM) == 106_168_320 + 28_311_552
    # olmo-1b at 8 layers: 8 × (4·2048² + 3·2048·8192) + 50304·2048
    assert flops.layer_matmul_params(OLMO) == 67_108_864
    assert flops.matmul_params(OLMO) == 536_870_912 + 103_022_592


def test_decode_step_by_hand():
    # smollm, batch 8, 641 positions: weights, K and V read up to the
    # length (30 layers × 2 × 3 heads × 64 × 2 bytes = 23,040 per
    # position), the new position written
    assert flops.kv_bytes_per_position(SMOLLM) == 23_040
    assert flops.decode_step_bytes(SMOLLM, 8, 641) == \
        2 * 134_479_872 + 8 * 641 * 23_040 + 8 * 23_040
    assert flops.decode_step_flops(SMOLLM, 8, 641) == \
        8 * (2 * 134_479_872 + 4 * 9 * 64 * 641 * 30)
    # olmo: 8 layers × 2 × 16 × 128 × 2 bytes
    assert flops.kv_bytes_per_position(OLMO) == 65_536


def test_flash_decode_call_by_hand():
    ops, byts = flops.flash_decode_call(SMOLLM, 8, 100)
    assert ops == 4 * 8 * 9 * 64 * 100 == 1_843_200
    assert byts == (2 * 8 * 100 * 3 * 64 + 2 * 8 * 9 * 64) * 2 == 632_832


def test_prefill_and_train_by_hand():
    n = 134_479_872
    attn = 4 * 9 * 64 * (512 * 513 / 2) * 30     # one causal sequence
    assert flops.attention_flops(SMOLLM, 512, 512, causal=True) == attn
    assert flops.train_step_flops(SMOLLM, 8, 512) == \
        6 * n * 8 * 512 + 3 * 8 * attn
    layers = 30 * 3_538_944
    assert flops.prefill_flops(SMOLLM, 8, 512) == \
        2 * layers * 8 * 512 + 2 * 8 * 576 * 49152 + 8 * attn
    peak = catalog.peaks()["TPU v5 lite"]
    # a decode step of batch 8 reads far more than it computes
    t = flops.least_time_s(flops.decode_step_flops(SMOLLM, 8, 641),
                           flops.decode_step_bytes(SMOLLM, 8, 641), peak)
    assert t == flops.decode_step_bytes(SMOLLM, 8, 641) / 819e9


# -- the dense family, pinned -------------------------------------------------

#: recorded from the dense code as it stood before it moved into
#: ``families/dense.py``, on the CPU: for smollm-135m and TINY, every
#: count the readers use and, at each seed, each weight leaf's float64 sum
#: and CRC-32, the reference's last logits on a seeded prompt (sum and
#: CRC-32), and three AdamW steps' losses, first-gradient norms and
#: update norms
GOLDEN = json.loads((CHECKOUT / "bench" / "testdata" /
                     "dense_golden.json").read_text())
GOLDEN_CONFIGS = {"smollm-135m": SMOLLM, "tiny": TINY}
GOLDEN_SHAPES = {"prefill": [(2, 16), (8, 128), (8, 512), (8, 1024)],
                 "ctx": [(2, 17), (8, 129), (8, 641), (8, 1056)],
                 "train": [(4, 32), (8, 512)]}


def _dense_counts(sz):
    out = {}
    for b, s in GOLDEN_SHAPES["prefill"]:
        out[f"prefill_flops.{b}.{s}"] = flops.prefill_flops(sz, b, s)
        out[f"prefill_bytes.{b}.{s}"] = flops.prefill_bytes(sz, b, s)
    for b, c in GOLDEN_SHAPES["ctx"]:
        out[f"decode_step_flops.{b}.{c}"] = flops.decode_step_flops(sz, b, c)
        out[f"decode_step_bytes.{b}.{c}"] = flops.decode_step_bytes(sz, b, c)
        out[f"flash_decode_call.{b}.{c}"] = list(
            flops.flash_decode_call(sz, b, c))
    for b, s in GOLDEN_SHAPES["train"]:
        out[f"train_step_flops.{b}.{s}"] = flops.train_step_flops(sz, b, s)
    return out


def _dense_readings(sz, seed):
    import zlib

    import jax
    import numpy as np

    from bench import harness, model, reference
    params = model.make_params(sz, seed)
    weights = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(x)
        weights[jax.tree_util.keystr(path)] = [
            float(a.astype(np.float64).sum()), zlib.crc32(a.tobytes())]
    prompt = model.make_tokens(sz, seed, model.PROMPTS, {"p": (2, 12)})["p"]
    lg = np.asarray(reference.last_logits(sz, params, prompt, 3))
    opt = catalog.traffic("rt3-over-train.smollm-135m")["be"]["optimizer"]
    mix = {"be": {"batch": 2, "seq": 16, "feed_pool": 3, "optimizer": opt}}
    tr_ = harness.reference_train(sz, mix, seed, None, 3)
    return {"weights": weights,
            "logits": [float(lg.astype(np.float64).sum()),
                       zlib.crc32(lg.tobytes())],
            "losses": tr_["losses"], "grad": tr_["grad"],
            "change": tr_["change"]}


@pytest.mark.parametrize("name", sorted(GOLDEN_CONFIGS))
def test_dense_counts_are_pinned(name):
    sz = GOLDEN_CONFIGS[name]
    assert _dense_counts(sz) == GOLDEN[name]["counts"]
    assert flops.decode_attention_layers(sz) == sz["num_hidden_layers"]


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 12345])
@pytest.mark.parametrize("name", sorted(GOLDEN_CONFIGS))
def test_dense_weights_and_reference_are_pinned(name, seed):
    """Bit for bit: the seed's weights, the reference's logits, and three
    reference AdamW steps through ``harness.reference_train``."""
    assert _dense_readings(GOLDEN_CONFIGS[name], seed) == \
        GOLDEN[name]["seeds"][str(seed)]


def test_attention_without_rope_sees_no_positions():
    """Without ``rope_theta`` the shared reference attention has no
    positional encoding: the last position's output is the same whatever
    the order of the positions before it.  With RoPE it is not."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    sz = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8}
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    shapes = {"wq": (16, 32), "wk": (16, 16), "wv": (16, 16), "wo": (32, 16)}
    c = {n: jax.random.normal(kk, s) for (n, s), kk in zip(shapes.items(), k)}
    y = jax.random.normal(k[4], (1, 6, 16))
    moved = y[:, jnp.array([2, 0, 4, 1, 3, 5])]

    def last(sz, y):
        return reference.attention(sz, None, y, c)[:, -1]
    assert jnp.allclose(last(sz, y), last(sz, moved), atol=1e-5)
    roped = dict(sz, rope_theta=10000.0)
    assert not jnp.allclose(last(roped, y), last(roped, moved), atol=1e-3)


# -- response from the due time ----------------------------------------------

def test_late_start_counts_as_late():
    """Period and deadline 1 s.  Release 0 overruns to 1.5 s, so release
    1 (due at 1 s) starts late and ends at 2.2 s: 1.2 of its deadline,
    missed, though it ran only 0.7 s.  Release 3 never completes."""
    dues = traffic.due_times(0.0, 1.0, 3.5)
    assert dues == [0.0, 1.0, 2.0, 3.0]
    out = traffic.outcomes(dues, {0: 1.5, 1: 2.2, 2: 2.6}, 1.0,
                           horizon=5.0)
    ratios = [pytest.approx(x) for x in (1.5, 1.2, 0.6, 2.0)]
    assert [r for r, _ in out] == ratios
    assert [ok for _, ok in out] == [False, False, True, False]


def test_a_late_slice_is_reported_not_judged():
    """A train slice of 1.9 s against ε of 317 ms, as a machine that
    stands still reads on the host clock: the guarantee's timing reports
    it, and it decides nothing; a release left unfinished does."""
    from types import SimpleNamespace as NS

    from bench import harness
    mix = {"rt": [{"name": "hi"}]}
    jobs = {"hi": NS(error=None, stats=NS(mort=1.2, slice_times=[0.01])),
            "be_train": NS(error=None, stats=NS(mort=None,
                                                slice_times=[0.12, 1.9]))}
    ev = {"jobs": jobs, "stuck": [], "epsilon_ms": 317.0,
          "decisions": {"hi": {"wcrt": {"hi": 2400.0}}, "be_train": {}}}
    tim = harness.timing(ev, mix)
    assert tim["slice_over_eps"] == pytest.approx(1900 / 317)
    assert tim["mort_over_wcrt"] == pytest.approx(0.5)
    checks = harness.judge({}, {}, harness.guarantee(ev, mix))
    assert set(checks) == {"job_errors", "unfinished"}
    assert not harness.over(checks)
    ev["stuck"] = [{"task": "hi", "k": 3}]
    assert harness.over(harness.judge({}, {}, harness.guarantee(ev, mix))) \
        == ["unfinished"]


def test_offsets_are_fixed_phases():
    mix = {"rt": [{"name": "a", "period_ms": 100.0, "phase": 0.1},
                  {"name": "b", "period_ms": 50.0, "phase": 0.5}]}
    assert traffic.offsets_ms(mix, {"a": 100.0, "b": 50.0}) == \
        {"a": pytest.approx(10.0), "b": 25.0}
    assert traffic.offsets_ms(mix, {"a": 100.0, "b": 10.0})["b"] == 5.0
    with pytest.raises(KeyError):
        traffic.offsets_ms({"rt": [{"name": "c", "period_ms": 1.0}]},
                           {"c": 1.0})


def test_percentile_and_sample():
    assert traffic.percentile([3.0], 95) == 3.0
    assert traffic.percentile(list(range(101)), 50) == 50.0
    picked = traffic.sample({"sample_per_task": 1},
                            {"a": [0, 1, 2], "b": [4]}, 9)
    assert picked["b"] == [4] and len(picked["a"]) == 1
    assert picked == traffic.sample({"sample_per_task": 1},
                                    {"a": [0, 1, 2], "b": [4]}, 9)


# -- discovery by name -------------------------------------------------------

@pytest.fixture
def bench_copy(tmp_path):
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    return tmp_path


def test_new_files_are_found_by_name(bench_copy):
    """A configuration, a traffic mix, limits and a per-layer metric
    added as files, and a cell naming them, need no code edit."""
    b = bench_copy / "bench"
    cfg = dict(SMOLLM, num_hidden_layers=12, reduced=["num_hidden_layers"])
    (b / "configs" / "smollm-12.json").write_text(json.dumps(cfg))
    mix = catalog.traffic("rt3-over-train.smollm-135m")
    mix["rt"] = mix["rt"][:1]
    (b / "traffic" / "one-task.json").write_text(json.dumps(mix))
    (b / "limits" / "smollm-12.one-task.json").write_text(
        json.dumps({"serve_gap": 1.0}))
    (b / "metrics" / "release_count.py").write_text(
        "def read(run):\n    return float(len(run.releases))\n")
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "smollm-12.one-task",
                              "config": "smollm-12",
                              "traffic": "one-task", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "release_count", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "executor", "moves": "rt_resp_p95",
                              "workloads": ["smollm-12.one-task"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))

    assert catalog.cell("smollm-12.one-task", bench_copy)["config"] == \
        "smollm-12"
    assert catalog.config("smollm-12", b)["num_hidden_layers"] == 12
    assert [t["name"] for t in catalog.traffic("one-task", b)["rt"]] == \
        ["hi"]
    assert catalog.limits("smollm-12.one-task", b) == {"serve_gap": 1.0}

    class R:
        releases = [{}, {}]
    assert catalog.metric_reader("release_count", b)(R()) == 2.0
    names = [m["name"] for m in catalog.metrics_of(
        "smollm-12.one-task", "per_layer", bench_copy)]
    assert names == ["release_count"]
    assert "release_count" not in [m["name"] for m in catalog.metrics_of(
        "smollm-135m.rt3-over-train", "per_layer", bench_copy)]


def test_every_metric_of_benchmark_has_a_reader():
    spec = catalog.benchmark()
    for m in spec["per_layer"]:
        assert callable(catalog.metric_reader(m["name"]))
    for w in spec["workloads"]:
        catalog.config(w["config"])
        catalog.traffic(w["traffic"])
        catalog.limits(w["name"])


# -- refusals ----------------------------------------------------------------

def _run_py(cwd: Path, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), "--workload",
         "smollm-135m.rt3-over-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_refuses_the_cpu_backend():
    r = _run_py(CHECKOUT, {})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_run_refuses_interpreted_kernels():
    r = _run_py(CHECKOUT, {"REPRO_PALLAS": "interpret"})
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "REPRO_PALLAS" in r.stderr


def test_run_refuses_without_the_program(bench_copy):
    r = _run_py(bench_copy, {})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    from bench import harness

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.Refused, match="no peaks"):
        harness.guard(1, catalog.peaks())


# -- whole runs with the timed path broken -----------------------------------

#: limits for the tiny cell, set from its readings on the CPU: sound runs
#: read about 0.003, 4e-5, 0.001 and 0.01-0.02; the fp8 reference reads
#: a logit gap of 0.038, a step on half its batch a gradient gap of 0.032
TINY_LIMITS = {"serve_gap": 0.015, "train_loss_gap": 0.001,
               "train_grad_gap": 0.01, "train_update_gap": 0.05}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout whose one cell is smollm-135m cut to 2 layers of width
    72 with a 128-token vocabulary, under a small RT mix."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "bench"
    cfg = dict(TINY, reduced=TINY_REDUCED)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = catalog.traffic("rt3-over-train.smollm-135m")
    mix["rt"] = [dict(name="hi", batch=2, prompt=16, new_tokens=8,
                      slice_tokens=4, period_ms=600.0, priority=50,
                      phase=0.1),
                 dict(name="mid", batch=2, prompt=32, new_tokens=16,
                      slice_tokens=4, period_ms=1500.0, priority=40,
                      phase=0.45)]
    mix["be"].update(batch=4, seq=32)
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny.mix.json").write_text(json.dumps(TINY_LIMITS))
    spec = catalog.benchmark()
    spec["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.mix", "tiny.alone"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _tiny_run(root, monkeypatch, seed=5, controls=False, cell="tiny.mix",
              log=lambda *a: None):
    from bench import harness
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    ops.set_use_pallas(True, interpret=True)
    try:
        return harness.run(cell, seed, 1.5, False, checkout=root,
                           need_tpu=False, controls=controls, log=log)
    finally:
        ops.set_use_pallas(None)


def _failed(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_sound_tiny_run_is_correct(tiny, monkeypatch):
    from bench import harness
    out = _tiny_run(tiny, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["checks"]) == set(harness.COMPARED) | {
        "job_errors", "unfinished", "be_stalled"}
    assert set(out["metrics"]) == {"rt_resp_p95", "rt_resp_p50",
                                   "be_tokens_per_s", "setup_s"}


def test_control_reads_above_the_program(tiny, monkeypatch):
    """The same run judged twice more, each control's readings in the
    program's place: the fp8 reference and a step that drops half its
    batch each come out not correct on a number compared with the
    reference, which the program's readings pass."""
    from bench import harness
    out = _tiny_run(tiny, monkeypatch, seed=6, controls=True)
    assert not set(harness.COMPARED) & _failed(out), out["checks"]
    assert set(out["controls"]) == {"fp8", "half_batch"}
    assert out["controls"]["fp8"]["correct"] is False
    assert "serve_gap" in out["controls"]["fp8"]["over"]
    assert out["controls"]["half_batch"]["correct"] is False
    assert "train_grad_gap" in out["controls"]["half_batch"]["over"]
    assert list(out)[-1] == "checks"


def _freeze_train_state(monkeypatch):
    """The fault of a train step that returns its state unchanged."""
    import repro.launch.steps as steps
    real = steps.build_train_step

    def frozen(cfg, opt_cfg=None):
        step = real(cfg, opt_cfg)

        def f(params, opt, batch):
            _, _, m = step(params, opt, batch)
            return params, opt, m
        return f
    monkeypatch.setattr(steps, "build_train_step", frozen)


def test_state_left_unchanged_is_caught(tiny, monkeypatch):
    _freeze_train_state(monkeypatch)
    out = _tiny_run(tiny, monkeypatch)
    assert not out["correct"]
    assert {"train_grad_gap", "train_update_gap"} <= _failed(out)


def test_half_batch_is_caught(tiny, monkeypatch):
    import repro.launch.steps as steps
    real = steps.build_train_step

    def halved(cfg, opt_cfg=None):
        step = real(cfg, opt_cfg)

        def f(params, opt, batch):
            n = batch["labels"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()})
        return f
    monkeypatch.setattr(steps, "build_train_step", halved)
    out = _tiny_run(tiny, monkeypatch)
    assert not out["correct"]
    assert "train_grad_gap" in _failed(out)


def test_altered_token_is_caught(tiny, monkeypatch):
    from repro.launch.serve import InferenceEngine
    real = InferenceEngine.decode_segment

    def altered(self, n, slice_tokens=1):
        op = real(self, n, slice_tokens)
        fin = op.finalize

        def finalize(carry):
            out = fin(carry)
            return out.at[0, n // 2].set((out[0, n // 2] + 1)
                                         % self.cfg.vocab_size)
        op.finalize = finalize
        return op
    monkeypatch.setattr(InferenceEngine, "decode_segment", altered)
    out = _tiny_run(tiny, monkeypatch)
    assert not out["correct"]
    assert "serve_gap" in _failed(out)


def test_a_mix_without_training_runs(tiny, monkeypatch):
    """A mix with no ``be`` key is RT serving alone: no training check, no
    BE throughput, the rest as before."""
    b = tiny / "bench"
    mix = json.loads((b / "traffic" / "tiny-mix.json").read_text())
    del mix["be"]
    (b / "traffic" / "tiny-alone.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny.alone.json").write_text(
        json.dumps({"serve_gap": TINY_LIMITS["serve_gap"]}))
    spec = json.loads((tiny / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.alone", "config": "tiny",
                              "traffic": "tiny-alone", "chips": 1,
                              "why": "test"})
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))
    from bench import harness
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    ops.set_use_pallas(True, interpret=True)
    try:
        out = harness.run("tiny.alone", 3, 1.5, False, checkout=tiny,
                          need_tpu=False, log=lambda *a: None)
    finally:
        ops.set_use_pallas(None)
    assert out["correct"], out["checks"]
    assert "be_tokens_per_s" not in out["metrics"]
    assert "train_grad_gap" not in out["checks"]


# -- a family and a BE configuration brought as new files --------------------

#: a second family, as a later configuration brings one: dense layers in a
#: pattern of attention positions, each stacked over its share of the
#: layers, and an untied unembedding; its counts are dense's
PAIRED_FAMILY = '''\
"""Dense layers in a pattern of attention positions, each stacked over
its share of the layers, and an untied unembedding; counted as dense."""
import functools

import jax
import jax.numpy as jnp

from bench import catalog, reference

dense = catalog.family({"family": "dense"})
FIELDS = dense.FIELDS


def __getattr__(name):
    return getattr(dense, name)


def program_fields(sz):
    from repro.models.blocks import BlockSpec
    return {"layer_pattern": ("pattern", tuple(
        BlockSpec(k) for k in sz["layer_pattern"]))}


def init(sz, k):
    n = len(sz["layer_pattern"])
    ks = jax.random.split(k, n + 1)
    per = dict(sz, num_hidden_layers=sz["num_hidden_layers"] // n,
               tie_word_embeddings=True)
    parts = [dense.init(per, kk) for kk in ks[:n]]
    d, v = sz["hidden_size"], sz["vocab_size"]
    un = jax.random.normal(ks[n], (d, v), jnp.float32) * d ** -0.5
    return dict(parts[0], blocks=[p["blocks"][0] for p in parts],
                unembed=un.astype(jnp.bfloat16))


def trunk(sz, quant, params, tokens):
    x = params["embed"][tokens].astype(reference.F32)
    one = jax.checkpoint(functools.partial(dense.layer, sz, quant))

    def repeat(x, ps):
        for p in ps:
            x = one(x, p)
        return x, None
    x, _ = jax.lax.scan(repeat, x, params["blocks"])
    return reference._norm(sz, x, params.get("final_norm_w"))


def last_logits(sz, params, tokens, n_last, quant=None, rows=2):
    return reference.trunk_last_logits(trunk, sz, params, tokens, n_last,
                                       quant, rows)


def lm_loss(sz, params, inputs, labels, z_loss, quant=None):
    return reference.trunk_lm_loss(trunk, sz, params, inputs, labels,
                                   z_loss, quant)
'''

#: the BE job's own configuration is smollm-135m's layout at other sizes,
#: with this vocabulary
BE_VOCAB = 160
#: limits for the paired cell, set from its readings on the CPU over seeds
#: 7-12: sound runs read 0-0.028, 1.8e-5-3.2e-5, 0.0013-0.0025 and
#: 0.0088-0.0164; the fp8 reference 0.070-0.70 (logit gap) and a step on
#: half its batch 1.4e-3-4.4e-3, 0.066-0.20 and 0.073-0.15; a state left
#: unchanged reads 1 on both norm gaps
PAIRED_LIMITS = {"serve_gap": 0.05, "train_loss_gap": 3e-4,
                 "train_grad_gap": 0.008, "train_update_gap": 0.04}


def _tree(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def paired(tmp_path_factory):
    """A checkout with one cell whose family, served configuration, BE
    configuration, mix and limits are new files beside an unchanged copy
    of the benchmark; returns (root, the copy's files before, the new
    files)."""
    root = tmp_path_factory.mktemp("paired")
    shutil.copytree(CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "bench"
    before = _tree(b)
    served = dict(TINY, family="paired", num_hidden_layers=4,
                  layer_pattern=["attn", "attn"], tie_word_embeddings=False,
                  reduced=TINY_REDUCED + ["layer_pattern",
                                          "tie_word_embeddings"])
    trained = dict(SMOLLM, num_hidden_layers=2, hidden_size=48,
                   num_attention_heads=6, num_key_value_heads=2, head_dim=8,
                   intermediate_size=128, vocab_size=BE_VOCAB,
                   reduced=TINY_REDUCED + ["num_attention_heads",
                                           "num_key_value_heads"])
    mix = catalog.traffic("rt3-over-train.smollm-135m")
    mix["rt"] = [dict(name="hi", batch=2, prompt=16, new_tokens=8,
                      slice_tokens=4, period_ms=600.0, priority=50,
                      phase=0.1)]
    mix["be"].update(batch=4, seq=32, config="tiny-be")
    new = {"families/paired.py": PAIRED_FAMILY,
           "configs/tiny-paired.json": json.dumps(served),
           "configs/tiny-be.json": json.dumps(trained),
           "traffic/over-tiny-be.json": json.dumps(mix),
           "limits/tiny-paired.over-be.json": json.dumps(PAIRED_LIMITS)}
    for rel, text in new.items():
        (b / rel).write_text(text)
    spec = catalog.benchmark()
    spec["workloads"] = [{"name": "tiny-paired.over-be",
                          "config": "tiny-paired", "traffic": "over-tiny-be",
                          "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-paired.over-be"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before, set(new)


def test_new_family_and_be_config_need_only_new_files(paired, monkeypatch):
    """The served model is the new family's, the BE job trains the
    configuration its mix names, and the run comes out correct with no
    file of the benchmark changed."""
    root, before, new = paired
    lines = []
    out = _tiny_run(root, monkeypatch, seed=7, cell="tiny-paired.over-be",
                    log=lines.append)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and "be_tokens_per_s" in out["metrics"]
    after = _tree(root / "bench")
    assert set(after) == set(before) | new
    assert {k: v for k, v in after.items() if k not in new} == before
    # the first losses are those of the BE configuration's vocabulary
    losses = json.loads(lines[-1])["info"]["train_losses"]
    for x in losses["program"][:1] + losses["reference"][:1]:
        assert abs(x - math.log(BE_VOCAB)) < 0.1, losses


def test_new_be_config_state_left_unchanged_is_caught(paired, monkeypatch):
    _freeze_train_state(monkeypatch)
    out = _tiny_run(paired[0], monkeypatch, seed=7,
                    cell="tiny-paired.over-be")
    assert not out["correct"]
    assert {"train_grad_gap", "train_update_gap"} <= _failed(out)
