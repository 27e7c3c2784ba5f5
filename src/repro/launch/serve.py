"""Serving driver: batched prefill + sliced decode, executor-ready.

The engine exposes device work as GPU-access segments (`repro.core.
segments.SlicedOp`): ``decode_segment(n)`` is a sliced, resumable segment
— ``slice_tokens`` decode programs per dispatch, with the KV cache /
position / emitted tokens threaded as the explicit carry — so the
real-time executor preempts between slices with delay bounded by one
slice, and a checkpoint can snapshot the carry mid-generation.  This is
the TPU analogue of the paper's thread-block-granularity preemption
window (DESIGN.md §6).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --batch 4 --prompt-len 32 --decode 64

``--n-devices N`` (N > 1) switches to disaggregated serving: a prefill
pool on device 0 and a decode pool on device N-1, each driven by its own
preemptive ``DeviceExecutor`` inside a ``ClusterExecutor`` whose
placement-aware admission pins the pools to their devices (DESIGN.md
§7).  The KV cache is handed off between pools with an explicit
``device_put``.  On a CPU host, expose devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get
from ..core.segments import SlicedOp, n_slices_for
from ..kernels.decode_attention import cache_len_for
from ..models import transformer
from ..runtime import enable_compile_cache, visible_devices
from ..sched import spans

#: decode tokens per dispatch of the served paths (the preemption grain)
SLICE_TOKENS = 4


class InferenceEngine:
    def __init__(self, cfg, params=None, max_len: int = 256, seed: int = 0,
                 device=None):
        """``device`` (a ``jax.Device``) places the params — and therefore
        every jitted program — on one accelerator of a multi-device host;
        None keeps the platform default.  ``max_len`` is rounded up to a
        length the decode kernel's cache blocks tile (the kernel masks
        past each row's length), so any prompt + output length fits."""
        self.cfg = cfg
        self.device = device
        self.params = params if params is not None else \
            transformer.init_params(cfg, jax.random.PRNGKey(seed))
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.max_len = max_len = cache_len_for(max_len)
        self._prefill = jax.jit(
            lambda p, t: transformer.prefill(cfg, p, t, max_len))
        self._decode = jax.jit(
            lambda p, c, tok, pos: transformer.decode_step(cfg, p, c, tok,
                                                           pos))
        self.cache = None
        self.pos = None
        self.last_tok = None
        self.last_out = None    # (B, n) tokens of the last finished decode

    def prefill_batch(self, tokens: jax.Array):
        """tokens: (B, S).  Returns last-token logits."""
        if self.device is not None:
            tokens = jax.device_put(tokens, self.device)
        logits, self.cache, self.pos = self._prefill(self.params, tokens)
        self.last_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits

    def adopt_state(self, cache, pos, last_tok) -> None:
        """Take over another engine's KV state — the prefill→decode
        handoff of disaggregated serving.  The state is ``device_put``
        onto this engine's device (the explicit cross-device transfer
        the placement layer charges to the handoff, not to a segment)."""
        if self.device is not None:
            cache, pos, last_tok = jax.device_put(
                (cache, pos, last_tok), self.device)
        self.cache, self.pos, self.last_tok = cache, pos, last_tok

    # -- GPU-access segments (executor-dispatched) ----------------------
    def prefill_segment(self, tokens: jax.Array) -> SlicedOp:
        """Prefill as a one-slice device segment (a single XLA program;
        its measured duration is its own preemption-delay bound)."""
        def step(carry, i):
            return self.prefill_batch(tokens)

        return SlicedOp(1, lambda: None, step, lambda logits: logits,
                        label="prefill")

    def decode_segment(self, n: int, slice_tokens: int = 1) -> SlicedOp:
        """Generate ``n`` tokens as a sliced segment: ``slice_tokens``
        jitted decode programs per dispatch (the preemption grain), carry
        = {cache, pos, tok, out}.  The engine state is committed at
        finalize, so a preempted/abandoned carry never corrupts the
        engine; ``finalize`` returns the (B, n) tokens."""
        b = self.last_tok.shape[0]

        def init():
            return {"cache": self.cache, "pos": self.pos,
                    "tok": self.last_tok,
                    "out": jnp.zeros((b, n), jnp.int32)}

        def step(carry, i):
            cache, pos, tok, out = (carry["cache"], carry["pos"],
                                    carry["tok"], carry["out"])
            lo, hi = i * slice_tokens, min((i + 1) * slice_tokens, n)
            # the host's enqueue of the slice's programs: it returns
            # before the device is done with them
            with spans.span("serve.decode_enqueue", tokens=hi - lo):
                for t in range(lo, hi):
                    logits, cache = self._decode(self.params, cache, tok,
                                                 pos)
                    pos = pos + 1
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    out = jax.lax.dynamic_update_slice(
                        out, tok[:, None], (0, t))
            return {"cache": cache, "pos": pos, "tok": tok, "out": out}

        def finalize(carry):
            self.cache = carry["cache"]
            self.pos = carry["pos"]
            self.last_tok = carry["tok"]
            self.last_out = carry["out"]
            return carry["out"]

        return SlicedOp(n_slices_for(n, slice_tokens), init, step,
                        finalize, label="decode")

    def decode_chunk(self, n: int, greedy: bool = True):
        """Generate ``n`` tokens inline (no executor): runs the sliced
        segment to completion.  Returns (B, n) tokens."""
        return self.decode_segment(n).run()

    def teacher_forced_logits(self, tokens: jax.Array, inputs: jax.Array):
        """Prefill ``tokens`` (B, S), then feed ``inputs[:, j]`` at decode
        step j instead of the engine's own choice — how a served
        generation is re-scored by another kernel path.  Returns the
        prefill's last-position logits (B, V) and every step's logits
        (n, B, V)."""
        first = self.prefill_batch(tokens)
        cache, pos = self.cache, self.pos
        steps = []
        for j in range(inputs.shape[1]):
            logits, cache = self._decode(self.params, cache, inputs[:, j],
                                         pos)
            steps.append(logits)
            pos = pos + 1
        return first, jnp.stack(steps)


def random_prompts(cfg, batch: int, prompt_len: int, seed: int = 1):
    """The seeded (batch, prompt_len) prompts the serving entry points use."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, prompt_len),
                              0, cfg.vocab_size)


def assert_placed(tree, device, what: str) -> None:
    """Raise unless every array of ``tree`` lives on ``device`` alone."""
    where = {d for x in jax.tree.leaves(tree) for d in x.devices()}
    if where != {device}:
        raise RuntimeError(f"{what} is on {sorted(map(str, where))}, "
                           f"but was placed on {device}")


def run_disaggregated(cfg, *, n_devices: int, batch: int, prompt_len: int,
                      decode: int) -> dict:
    """Prefill and decode pools on separate devices: the classic
    disaggregated-serving scenario, on the cluster runtime.  Each pool
    is an RT job pinned to its device; admission runs the cross-device
    analysis on the pinned placements before either job may start.
    Submission goes through the unified facade (``repro.sched.connect``
    → ``SchedClient``, DESIGN.md §9) — the bodies still bracket their
    device segments on the executor face via ``client.cluster``.

    Returns the decoded (batch, decode) tokens, both engines (whose
    params and caches are checked to sit on their pool's device, the
    decode cache after the handoff) and the per-device MORTs."""
    from ..sched import JobProfile, connect

    devs = visible_devices(n_devices)
    prefill_dev, decode_dev = 0, n_devices - 1
    max_len = prompt_len + decode
    pre = InferenceEngine(cfg, max_len=max_len, device=devs[prefill_dev])
    dec = InferenceEngine(cfg, params=pre.params, max_len=max_len,
                          device=devs[decode_dev])
    toks = random_prompts(cfg, batch, prompt_len)

    # warm-up + nominal WCETs for the admission profiles (margin below)
    pre.prefill_batch(toks)
    jax.block_until_ready(pre.cache)
    dec.adopt_state(pre.cache, pre.pos, pre.last_tok)
    dec.decode_chunk(2)
    t0 = time.perf_counter()
    pre.prefill_batch(toks)
    jax.block_until_ready(pre.cache)
    prefill_ms = (time.perf_counter() - t0) * 1e3
    dec.adopt_state(pre.cache, pre.pos, pre.last_tok)
    t0 = time.perf_counter()
    jax.block_until_ready(dec.decode_chunk(4))
    decode_ms = (time.perf_counter() - t0) * 1e3 / 4 * decode

    client = connect(n_devices=n_devices, policy="ioctl",
                     wait_mode="suspend", n_cpus=2, epsilon_ms=1.0)
    cluster = client.cluster
    handoff = threading.Event()
    out: dict = {}
    times: dict = {}

    def prefill_body(job, it):
        t = time.perf_counter()
        with cluster.device_segment(job):
            cluster.run_sliced(job, pre.prefill_segment(toks))
        dec.adopt_state(pre.cache, pre.pos, pre.last_tok)
        times["prefill_ms"] = (time.perf_counter() - t) * 1e3
        handoff.set()

    def decode_body(job, it):
        if not handoff.wait(timeout=120):
            raise RuntimeError("prefill pool never handed off")
        t = time.perf_counter()
        with cluster.device_segment(job):
            out["tokens"] = cluster.run_sliced(
                job, dec.decode_segment(decode, slice_tokens=SLICE_TOKENS))
        times["decode_ms"] = (time.perf_counter() - t) * 1e3

    period = max(prefill_ms + decode_ms, 1.0) * 20
    m = 3.0  # one observation is not a WCET
    r_pre = client.submit(
        JobProfile("prefill", [1.0], [(1.0, prefill_ms * m)],
                   period_ms=period, priority=40, cpu=0,
                   device=prefill_dev),
        body=prefill_body)
    r_dec = client.submit(
        JobProfile("decode", [1.0], [(1.0, decode_ms * m)],
                   period_ms=period, priority=50, cpu=1,
                   device=decode_dev),
        body=decode_body)
    # check both admissions before starting either pool: a refusal must
    # not leave the other pool's thread running behind an exception
    for tag, r in (("prefill", r_pre), ("decode", r_dec)):
        if not r.accepted:
            client.close(shutdown=True)
            raise SystemExit(f"{tag} pool refused admission "
                             f"({r.reason}): {r.error or r.wcrt}")
    print(f"admission: prefill -> device {r_pre['device']} "
          f"({r_pre['via']}), decode -> device {r_dec['device']} "
          f"({r_dec['via']})")
    assert r_pre["device"] != r_dec["device"]
    r_pre.job.start(cluster)
    r_dec.job.start(cluster)
    try:
        client.join(180)
    finally:
        client.close(shutdown=True)
    cluster.assert_migration_free()
    for job in (r_pre.job, r_dec.job):
        if job.error is not None:
            raise RuntimeError(f"{job.name} pool failed") from job.error
    if "tokens" not in out:
        raise RuntimeError("decode pool produced no tokens")
    assert_placed(pre.params, devs[prefill_dev], "prefill params")
    assert_placed(pre.cache, devs[prefill_dev], "prefill KV cache")
    assert_placed(dec.params, devs[decode_dev], "decode params")
    assert_placed(dec.cache, devs[decode_dev], "decode KV cache")

    per_tok = times["decode_ms"] / decode
    print(f"prefill pool (device {prefill_dev}): "
          f"{batch}x{prompt_len} in {times['prefill_ms']:.1f} ms")
    print(f"decode pool (device {decode_dev}): {decode} tokens, "
          f"{per_tok:.2f} ms/tok "
          f"({batch * 1e3 / per_tok:.1f} tok/s aggregate)")
    morts = client.per_device_mort()
    print("per-device MORT (s):",
          {d: (round(v, 3) if v is not None else None)
           for d, v in morts.items()})
    tokens = np.asarray(out["tokens"])
    print("sample:", tokens[0, :16])
    print("disaggregated serve OK")
    return {"tokens": tokens, "prefill": pre, "decode": dec,
            "per_device_mort": morts}


def register_serving_workloads(cfg, seed: int = 1) -> dict:
    """Register the serving segments in the durable-workload registry
    (``repro.sched.workloads``): ``serve.decode`` is a prefill + sliced
    decode whose carry (KV cache, position, emitted tokens) checkpoints
    mid-generation — a daemon submission of it survives a restart and
    resumes decoding at the journaled slice.  Each engine lives on the
    JAX device of the cluster device its job is bound to.  Returns the
    engines as they are built, keyed (device, batch, prompt_len,
    decode)."""
    from ..sched.workloads import register_workload

    engines: dict = {}

    def decode_factory(device: int = 0, batch: int = 2,
                       prompt_len: int = 16, decode: int = 32,
                       slice_tokens: int = SLICE_TOKENS):
        key = (device, batch, prompt_len, decode)
        eng = engines.get(key)
        if eng is None:
            eng = InferenceEngine(
                cfg, max_len=prompt_len + decode,
                device=visible_devices(device + 1)[device])
            engines[key] = eng
        eng.prefill_batch(random_prompts(cfg, batch, prompt_len, seed))
        return eng.decode_segment(decode, slice_tokens=slice_tokens)

    register_workload("serve.decode", decode_factory)
    return engines


def run_daemon(cfg, args) -> None:
    """Daemon mode: the serving workloads registered, then the durable
    scheduling daemon (`repro.sched.daemon`) owning the cluster — submit
    with ``python -m repro.sched.client --socket ... submit --workload
    serve.decode ...`` and the generation survives ``kill -9``."""
    import os
    import signal

    from ..sched.daemon import SchedDaemon

    visible_devices(args.n_devices)
    register_serving_workloads(cfg)
    daemon = SchedDaemon(args.store, args.socket,
                         n_devices=args.n_devices)
    daemon.start()
    print(f"serve daemon ready pid={os.getpid()} "
          f"socket={daemon.socket_path} "
          f"recovered={daemon.recovery['recovered']} "
          f"resumed={sorted(daemon.recovery['resumed'])}", flush=True)
    signal.signal(signal.SIGTERM, lambda *a: daemon._stop.set())
    try:
        daemon.serve_forever()
    finally:
        daemon.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode", type=int, default=64)
    ap.add_argument("--n-devices", type=int, default=1,
                    help="N>1: disaggregated prefill/decode pools on "
                         "separate devices via ClusterExecutor")
    ap.add_argument("--daemon", action="store_true",
                    help="run the durable scheduling daemon with the "
                         "serving workloads registered")
    ap.add_argument("--store", default=None,
                    help="daemon job-store directory (--daemon)")
    ap.add_argument("--socket", default=None,
                    help="daemon unix socket (--daemon; default "
                         "<store>/sock)")
    args = ap.parse_args()

    entry = get(args.arch)
    cfg = entry.reduced() if args.reduced else entry.config()
    enable_compile_cache()
    if args.daemon:
        if not args.store:
            ap.error("--daemon requires --store")
        run_daemon(cfg, args)
        return
    if args.n_devices > 1:
        run_disaggregated(cfg, n_devices=args.n_devices, batch=args.batch,
                          prompt_len=args.prompt_len, decode=args.decode)
        return
    eng = InferenceEngine(cfg, max_len=args.prompt_len + args.decode)
    toks = random_prompts(cfg, args.batch, args.prompt_len)
    t0 = time.perf_counter()
    eng.prefill_batch(toks)
    t1 = time.perf_counter()
    out = eng.decode_chunk(args.decode)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    print(f"prefill {args.batch}x{args.prompt_len}: {(t1 - t0) * 1e3:.1f} ms")
    per_tok = (t2 - t1) * 1e3 / args.decode
    print(f"decode {args.decode} tokens: {per_tok:.2f} ms/tok "
          f"({args.batch * 1e3 / per_tok:.1f} tok/s aggregate)")
    print("sample:", np.asarray(out[0, :16]))


if __name__ == "__main__":
    main()
