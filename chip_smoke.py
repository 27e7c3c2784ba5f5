#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: smollm-135m at its published widths, served
through the scheduler's normal entry points with compiled Pallas kernels.

  python3 chip_smoke.py             # one chip: phases (a)-(e)
  python3 chip_smoke.py --chips 4   # four chips: disaggregated serving only

One chip, in one process:
  (a) refuse to run anywhere but a TPU with the compiled kernels on;
  (b) in-process serving through ``connect()``: an RT job (prefill + sliced
      decode, batch 8, prompt 512, 128 new tokens, 4 tokens per slice)
      preempts a BE job of full-width train steps at slice boundaries; MORT
      within the admitted WCRT, no RT deadline miss, BE progress, no job
      error, no migration;
  (c) the served logits, re-scored through the ``kernels/ref.py`` path on
      the same chip on the tokens the served path emitted, within the
      tolerance below;
  (d) the scheduling daemon in-process, ``serve.decode`` registered: three
      requests through ``connect(<socket>)``, each profiled as one whole
      request with its carry checkpoints, all finish within their
      admitted WCRT with no deadline miss and none unaccounted;
  (e) the last line: {"ok": true, "device": {...}}.

``--chips 4`` runs disaggregated serving (prefill on device 0, decode on
device 3) against the same requests on one chip: equal tokens, and every
array on the device its placement named.  Any failed phase raises, and the
script exits non-zero without the last line.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {SRC}; run it from a "
             f"checkout of the repository")
sys.path.insert(0, str(SRC))

BATCH, PROMPT, DECODE, SLICE_TOKENS = 8, 512, 128, 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_MICROBATCHES = 8, 512, 2
DAEMON_REQUESTS = 3
# bf16 weights and activations through 30 layers: the compiled kernels
# and the references round differently, so each logit row set may differ
# by this share of its own norm (relative L2), and no logit by more than
# this share of the largest reference logit
REL_L2_TOL, MAX_ABS_TOL = 0.05, 0.1


class CompileMeter:
    """Seconds spent compiling (a persistent-cache hit counts only its
    read), programs compiled, and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits

    def since(self, snap) -> str:
        s, p, h = snap
        return (f"compile {self.seconds - s:.1f} s, "
                f"{self.programs - p} program(s), "
                f"{self.cache_hits - h} persistent-cache hit(s)")


def report(phase: str, meter: CompileMeter, snap, t0: float, **fields):
    print(f"[{phase}] {time.perf_counter() - t0:.1f} s wall, "
          f"{meter.since(snap)}; "
          + ", ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def refuse_unless_chip():
    """(a): the device, and the kernel path the model will take."""
    import jax

    from repro.kernels import ops
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX backend is {backend!r}, not a "
                         f"TPU; nothing was run")
    if ops.interpret_mode() or not ops.use_pallas():
        raise SystemExit("chip_smoke: Pallas kernels are off or in "
                         "interpret mode; nothing was run")
    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"count={len(devs)} kernels=pallas-compiled "
          f"(use_pallas={ops.use_pallas()}, "
          f"interpret={ops.interpret_mode()})", flush=True)
    return devs


def serve_preemptive(cfg, dev, *, batch, prompt_len, decode, slice_tokens,
                     train_batch, train_seq, train_microbatches):
    """(b): RT serving preempts BE training on one device via connect()."""
    from repro.launch.fleet import decode_workload, train_workload
    from repro.launch.preemptive import check_preemptive, run_preemptive
    from repro.launch.serve import InferenceEngine, random_prompts

    engine = InferenceEngine(cfg, max_len=prompt_len + decode, seed=0,
                             device=dev)
    prompt = random_prompts(cfg, batch, prompt_len)
    infer_wl = decode_workload("infer", engine, prompt, decode, slice_tokens)
    train_wl = train_workload("train", cfg, batch=train_batch,
                              seq_len=train_seq,
                              microbatches=train_microbatches, jdev=dev,
                              seed=2)
    result = run_preemptive(infer_wl, train_wl, policy="ioctl")
    check_preemptive(result)
    return engine, prompt, result


def compare_with_reference(cfg, dev, engine, prompt, served):
    """(c): teacher-forced re-scoring through kernels/ref.py."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch.serve import InferenceEngine

    served = jnp.asarray(served)
    first = jnp.argmax(engine.prefill_batch(prompt), axis=-1)
    inputs = jnp.concatenate([first[:, None].astype(jnp.int32),
                              served[:, :-1]], axis=1)
    got_first, got_steps = engine.teacher_forced_logits(prompt, inputs)
    # the replay is the served program on the served inputs: it must
    # re-emit the served tokens exactly
    replay = np.asarray(jnp.argmax(got_steps, axis=-1)).T
    if not np.array_equal(replay, np.asarray(served)):
        raise RuntimeError("replaying the served decode did not reproduce "
                           "the served tokens")
    ops.set_use_pallas(False)
    try:
        ref_engine = InferenceEngine(cfg, params=engine.params,
                                     max_len=engine.max_len, device=dev)
        want_first, want_steps = ref_engine.teacher_forced_logits(prompt,
                                                                  inputs)
    finally:
        ops.set_use_pallas(None)
    got = np.concatenate([np.asarray(got_first, np.float32)[None],
                          np.asarray(got_steps, np.float32)])
    want = np.concatenate([np.asarray(want_first, np.float32)[None],
                           np.asarray(want_steps, np.float32)])
    diff = got - want
    rel_l2 = (np.linalg.norm(diff.reshape(len(diff), -1), axis=1)
              / np.linalg.norm(want.reshape(len(want), -1), axis=1))
    max_abs = float(np.abs(diff).max() / np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    out = {"worst_rel_l2": float(rel_l2.max()),
           "worst_step": int(rel_l2.argmax()),
           "max_abs_over_max_ref": max_abs, "argmax_agreement": agree}
    if rel_l2.max() > REL_L2_TOL or max_abs > MAX_ABS_TOL:
        raise RuntimeError(f"served logits differ from the reference path "
                           f"beyond tolerance (relative L2 {REL_L2_TOL}, "
                           f"max abs {MAX_ABS_TOL}): {out}")
    return out


def time_request(factory, kwargs, ckpt_dir, every):
    """One ``serve.decode`` request as the daemon runs it: the factory's
    prefill, every decode slice, and a carry checkpoint after every
    ``every``-th slice.  Returns (request ms, longest slice ms,
    [checkpoint ms])."""
    import jax

    from repro.sched import save_carry

    t_req = time.perf_counter()
    op = factory(device=0, **kwargs)
    carry, longest, ckpts = op.init(), 0.0, []
    for i in range(op.n_slices):
        t0 = time.perf_counter()
        carry = jax.block_until_ready(op.step(carry, i))
        longest = max(longest, (time.perf_counter() - t0) * 1e3)
        if (i + 1) % every == 0:
            t0 = time.perf_counter()
            save_carry(ckpt_dir, "probe", i + 1, carry)
            ckpts.append((time.perf_counter() - t0) * 1e3)
    jax.block_until_ready(op.finalize(carry))
    return (time.perf_counter() - t_req) * 1e3, longest, ckpts


def serve_through_daemon(cfg, *, batch, prompt_len, decode, slice_tokens,
                         epsilon_ms, served):
    """(d): serve.decode requests over the daemon's socket, each within
    the WCRT its admission computed."""
    import jax
    import numpy as np

    from repro.launch import preemptive
    from repro.launch.serve import register_serving_workloads
    from repro.sched import JobProfile, connect
    from repro.sched.daemon import SchedDaemon
    from repro.sched.workloads import get_workload

    engines = register_serving_workloads(cfg)
    spec = {"name": "serve.decode",
            "kwargs": {"batch": batch, "prompt_len": prompt_len,
                       "decode": decode, "slice_tokens": slice_tokens}}
    n_slices = -(-decode // slice_tokens)
    # checkpoint the KV-cache carry twice per request, not per slice
    every = max(n_slices // 2, 1)
    # a refused or failed request leaves jobs writing checkpoints here
    # while the error propagates: the error, not the clean-up, is news
    with tempfile.TemporaryDirectory(prefix="smoke",
                                     ignore_cleanup_errors=True) as store:
        # a request's carry checkpoints hold its device segment, so its
        # profile is one whole request as the daemon runs it, measured
        # after a first request that builds and compiles the engine (as a
        # deployment would: a request should not pay for its server's
        # compile)
        factory = get_workload("serve.decode")
        probes = [time_request(factory, spec["kwargs"],
                               str(Path(store) / "probe"), every)
                  for _ in range(2)]
        request_ms, probe_slice_ms, checkpoint_ms = probes[-1]
        exec_ms = preemptive.MARGIN * request_ms
        # ε of phase (b) bounds a slice of either job there; it stands
        # unless these requests' own slices need a larger one
        epsilon_ms = max(epsilon_ms, preemptive.epsilon_ms(probe_slice_ms))
        period_ms = 4 * DAEMON_REQUESTS * (exec_ms + 4 * epsilon_ms)
        daemon = SchedDaemon(str(Path(store) / "daemon"), n_devices=1,
                             epsilon_ms=epsilon_ms, checkpoint_every=every)
        daemon.start()
        try:
            client = connect(daemon.socket_path)
            names = [f"req{i}" for i in range(DAEMON_REQUESTS)]
            for i, name in enumerate(names):
                dec = client.submit(
                    JobProfile(name, [1.0], [(1.0, exec_ms)],
                               period_ms=period_ms, priority=30 + i),
                    workload_spec=spec, n_iterations=1, start=True)
                if not dec.accepted:
                    raise RuntimeError(f"daemon refused {name}: "
                                       f"{dec.reason} {dec.error}")
            # the last decision prices every request against all three
            wcrt_ms = {n: dec.wcrt[n] for n in names}
            deadline = time.monotonic() + 600
            while True:
                jobs = client.jobs()
                done = [n for n in names
                        if jobs[n]["done_iterations"] >= 1]
                failed = {n: daemon.cluster.find_job(n).error
                          for n in names
                          if daemon.cluster.find_job(n).error is not None}
                if failed:
                    raise RuntimeError(f"daemon request(s) failed: "
                                       f"{failed}")
                if len(done) == len(names):
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(f"daemon requests unfinished: "
                                       f"{sorted(set(names) - set(done))}")
                time.sleep(0.2)
            audit = client._backend.request("audit")
            max_slice_ms = max(daemon.cluster.find_job(n).stats
                               .max_slice_time for n in names) * 1e3
        finally:
            daemon.stop()
    eng = engines[(0, batch, prompt_len, decode)]
    out = {"finished": len(names), "unaccounted": audit["unaccounted"],
           "request_ms": request_ms, "checkpoint_ms": checkpoint_ms,
           "carry_bytes": sum(x.nbytes for x in jax.tree.leaves(eng.cache)),
           "probe_max_slice_ms": probe_slice_ms, "exec_ms": exec_ms,
           "period_ms": period_ms, "wcrt_ms": wcrt_ms,
           "mort_ms": {n: jobs[n]["mort_s"] * 1e3 for n in names},
           "deadline_misses": sum(jobs[n]["deadline_misses"]
                                  for n in names),
           "max_slice_ms": max_slice_ms, "epsilon_ms": epsilon_ms}
    if audit["unaccounted"]:
        raise RuntimeError(f"daemon lost jobs: {audit}")
    late = {n: m for n, m in out["mort_ms"].items() if m > wcrt_ms[n]}
    if late or out["deadline_misses"]:
        raise RuntimeError(f"daemon requests broke the RT guarantee: "
                           f"MORT over WCRT {late}, "
                           f"{out['deadline_misses']} deadline miss(es); "
                           f"{out}")
    if max_slice_ms > epsilon_ms:
        raise RuntimeError(f"a daemon slice took {max_slice_ms:.1f} ms, "
                           f"longer than ε {epsilon_ms:.1f} ms: {out}")
    if not np.array_equal(np.asarray(eng.last_out), np.asarray(served)):
        raise RuntimeError("daemon-served tokens differ from the "
                           "connect()-served tokens of the same requests")
    return out


def disaggregated_vs_one_chip(cfg, devs, *, batch, prompt_len, decode):
    """--chips 4: prefill on device 0, decode on device 3, against the
    same requests on one chip; every array on its placed device."""
    import numpy as np

    from repro.launch.serve import (InferenceEngine, assert_placed,
                                    random_prompts, register_serving_workloads,
                                    run_disaggregated)
    from repro.sched import JobProfile, connect

    n = len(devs)
    # raises unless each pool's params and KV cache sit on its device,
    # the decode cache after the device_put handoff included
    out = run_disaggregated(cfg, n_devices=n, batch=batch,
                            prompt_len=prompt_len, decode=decode)

    one = InferenceEngine(cfg, max_len=prompt_len + decode, seed=0,
                          device=devs[0])
    one.prefill_batch(random_prompts(cfg, batch, prompt_len))
    want = np.asarray(one.decode_chunk(decode))
    if not np.array_equal(out["tokens"], want):
        raise RuntimeError("disaggregated tokens differ from one-chip "
                           "tokens of the same requests")

    # a registered serving workload builds its engine on the device its
    # job is bound to
    engines = register_serving_workloads(cfg)
    client = connect(n_devices=n, policy="ioctl", wait_mode="suspend")
    try:
        res = client.submit(
            JobProfile("pinned", [1.0], [(1.0, 1.0)], period_ms=60_000.0,
                       priority=10, device=n - 1),
            workload_spec={"name": "serve.decode",
                           "kwargs": {"batch": batch,
                                      "prompt_len": prompt_len,
                                      "decode": decode}},
            n_iterations=1, start=True)
        if not res.accepted:
            raise RuntimeError(f"pinned serve.decode refused: {res.error}")
        client.join(600)
    finally:
        client.close(shutdown=True)
    if res.job.error is not None:
        raise RuntimeError("pinned serve.decode failed") from res.job.error
    eng = engines[(n - 1, batch, prompt_len, decode)]
    assert_placed(eng.params, devs[n - 1], "daemon-workload params")
    assert_placed(eng.cache, devs[n - 1], "daemon-workload KV cache")
    if not np.array_equal(np.asarray(eng.last_out), want):
        raise RuntimeError("pinned serve.decode tokens differ from the "
                           "one-chip tokens")
    return {"tokens_equal": True, "prefill_device": str(devs[0]),
            "decode_device": str(devs[n - 1])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: disaggregated serving across four chips only")
    args = ap.parse_args()

    import jax

    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = refuse_unless_chip()
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{len(devs)} device(s) visible")
    devs = devs[:args.chips]
    meter = CompileMeter()
    print(f"compile cache: {cache_dir}", flush=True)

    from repro.configs import get
    cfg = get("smollm-135m").config()
    sizes = dict(batch=BATCH, prompt_len=PROMPT, decode=DECODE)

    if args.chips == 4:
        t0, snap = time.perf_counter(), meter.snapshot()
        res = disaggregated_vs_one_chip(cfg, devs, **sizes)
        report("disaggregated", meter, snap, t0, **res)
    else:
        t0, snap = time.perf_counter(), meter.snapshot()
        engine, prompt, res = serve_preemptive(
            cfg, devs[0], **sizes, slice_tokens=SLICE_TOKENS,
            train_batch=TRAIN_BATCH, train_seq=TRAIN_SEQ,
            train_microbatches=TRAIN_MICROBATCHES)
        served = engine.last_out
        report("serve", meter, snap, t0,
               rt_completions=res["rt_completions"],
               rt_mort_ms=res["rt_mort_ms"], wcrt_ms=res["wcrt_ms"],
               rt_deadline_misses=res["rt_deadline_misses"],
               be_releases=res["be_completions"],
               epsilon_ms=res["epsilon_ms"], period_ms=res["period_ms"],
               profiled_max_slice_ms=res["profiled_max_slice_ms"],
               max_slice_ms=res["max_slice_ms"],
               longest_slices_ms={n: t[-5:]
                                  for n, t in res["slice_ms"].items()})

        t0, snap = time.perf_counter(), meter.snapshot()
        res_ref = compare_with_reference(cfg, devs[0], engine, prompt, served)
        report("reference", meter, snap, t0, **res_ref)

        t0, snap = time.perf_counter(), meter.snapshot()
        res_d = serve_through_daemon(
            cfg, **sizes, slice_tokens=SLICE_TOKENS,
            epsilon_ms=res["epsilon_ms"], served=served)
        report("daemon", meter, snap, t0, **res_d)

    print(f"compile total {meter.seconds:.1f} s, {meter.programs} "
          f"program(s), {meter.cache_hits} persistent-cache hit(s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
