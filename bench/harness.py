"""One run of one cell: build, warm up, profile and admit; drive the
window through ``repro.sched.connect()``; then check what the window
produced against the plain reference, and reduce the spans, counters
and trace to the cell's metrics.

The system under test is the program's: ``connect()`` →
``ClusterExecutor`` (policy ``ioctl``, suspending waits) →
``SegmentedWorkload`` / ``SlicedOp``, RT tasks built from
``launch.serve.InferenceEngine``, the BE job from
``launch.steps.build_train_step``.  Everything else here is the
benchmark's: weights and inputs from the seed, the release schedule,
the timing wrappers around each ``SlicedOp`` and the comparison."""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import catalog, model, reference, traffic

PROCESS_START = time.monotonic()


class Refused(SystemExit):
    """The run cannot measure what the cell asks for; nothing printed."""


def guard(chips: int, peaks: dict, *, need_tpu: bool = True):
    """Refuse, before any work, a backend other than TPU, Pallas kernels
    off or interpreted, a device kind with no peaks, or too few chips."""
    env = os.environ.get("REPRO_PALLAS", "")
    if env and need_tpu:
        raise Refused(f"bench: REPRO_PALLAS={env} keeps the compiled "
                      f"kernels off the chip; nothing was run")
    import jax
    devs = jax.devices()
    if need_tpu and devs[0].platform != "tpu":
        raise Refused(f"bench: JAX backend is {devs[0].platform!r}, not a "
                      f"TPU; nothing was run")
    kind = devs[0].device_kind
    if need_tpu and kind not in peaks:
        raise Refused(f"bench: no peaks for device kind {kind!r} in "
                      f"peaks.json; nothing was run")
    if len(devs) < chips:
        raise Refused(f"bench: the cell asks for {chips} chip(s), "
                      f"{len(devs)} visible; nothing was run")
    return devs


def compile_cache(checkout: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program cached, whatever its compile time or size."""
    import jax
    path = checkout / ".bench_cache" / "jax"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


@dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: str
    sizes: dict
    mix: dict
    peak: dict
    #: the BE job's configuration: the cell's, unless the mix's ``be``
    #: names another under ``config``; None without a BE job
    be_sizes: dict | None = None
    #: one dict per timed slice: kind (prefill|decode|train), task,
    #: start, end (monotonic s), and for decode the first position and
    #: the tokens it emitted
    slices: list = field(default_factory=list)
    #: one dict per RT release due in the window: task, k, due, start
    #: (first device slice), done
    releases: list = field(default_factory=list)
    #: job name -> the program's JobStats
    job_stats: dict = field(default_factory=dict)
    #: timing() of the window: MORT over WCRT, longest slice over ε
    timing: dict = field(default_factory=dict)
    #: trace.reduce() of the traced window, or None
    trace: dict | None = None
    #: trace.load() of the traced window: device events, host spans
    trace_events: dict | None = None


def _timed(op, kind: str, task: str, sink: list, on_first=None,
           pos0: int = 0, slice_tokens: int = 0, n_tokens: int = 0):
    """``op`` with each step timed to the end of its device work and
    recorded in ``sink``, under a ``bench.*`` trace span."""
    import jax

    from repro.core.segments import SlicedOp
    span = {"prefill": "bench.rt_prefill", "decode": "bench.rt_decode_slice",
            "train": "bench.be_train_slice"}[kind]

    def step(carry, i):
        t0 = time.monotonic()
        if on_first is not None and i == 0:
            on_first(t0)
        args = ({"pos0": pos0 + i * slice_tokens,
                 "tokens": min(slice_tokens, n_tokens - i * slice_tokens)}
                if kind == "decode" else {})
        with jax.profiler.TraceAnnotation(span, task=task, **args):
            out = jax.block_until_ready(op.step(carry, i))
        sink.append({"kind": kind, "task": task, "start": t0,
                     "end": time.monotonic(), **args})
        return out

    return SlicedOp(op.n_slices, op.init, step, op.finalize, op.label)


class Cell:
    """The program's objects for one cell, built from the seed."""

    def __init__(self, c: dict, seed: int, device, bench_dir: Path):
        import jax

        from repro.launch.serve import InferenceEngine
        from repro.launch.steps import build_train_step
        from repro.optim import adamw

        self.spec = c
        self.sizes = catalog.config(c["config"], bench_dir)
        self.mix = catalog.traffic(c["traffic"], bench_dir)
        self.seed, self.device = seed, device
        self.cfg = model.program_config(self.sizes)
        self.params = model.make_params(self.sizes, seed, device)
        pool = self.mix["prompt_pool"]
        self.prompts = model.make_tokens(
            self.sizes, seed, model.PROMPTS,
            {t["name"]: (pool, t["batch"], t["prompt"])
             for t in self.mix["rt"]}, device)
        self.train = self.be_sizes = None
        self.slices: list = []
        self.current: dict = {}      # task -> the release in flight
        self.outputs: dict = {}      # (task, k) -> (first (B,), tokens (B,n))
        self.engines = {
            t["name"]: InferenceEngine(self.cfg, params=self.params,
                                       max_len=t["prompt"] + t["new_tokens"],
                                       device=device)
            for t in self.mix["rt"]}
        be = self.mix.get("be")
        if be is None:
            return
        # the BE job trains its own configuration: the cell's, or the one
        # the mix names; its weights and rows come from the same seed
        self.be_sizes = catalog.config(be.get("config", c["config"]),
                                       bench_dir)
        feed = model.make_tokens(
            self.be_sizes, seed, model.FEED,
            {"rows": (be["feed_pool"], be["batch"], be["seq"] + 1)},
            device)["rows"]
        self.feed = [{"inputs": feed[i, :, :-1], "labels": feed[i, :, 1:]}
                     for i in range(be["feed_pool"])]
        self.opt = dict(be["optimizer"])
        opt_cfg = adamw.AdamWConfig(**{k: v for k, v in self.opt.items()
                                       if k != "z_loss"})
        self._train_step = jax.jit(
            build_train_step(model.program_config(self.be_sizes), opt_cfg),
            donate_argnums=(0, 1))
        tp = model.make_params(self.be_sizes, seed, device)
        self.train = {"params": tp,
                      "opt": jax.jit(adamw.init_opt_state)(tp),
                      "steps": 0, "loss": None}

    # -- the BE job ----------------------------------------------------
    def train_op(self):
        """One optimizer step per slice on the next rows of the seeded
        feed; the state is committed at every step."""
        from repro.core.segments import SlicedOp
        st = self.train

        def step(carry, i):
            batch = self.feed[st["steps"] % len(self.feed)]
            p, o, m = self._train_step(st["params"], st["opt"], batch)
            st.update(params=p, opt=o, loss=m["loss"],
                      steps=st["steps"] + 1)
            return p, o

        return SlicedOp(self.mix["be"]["microbatches"], lambda: None, step,
                        lambda c: None, label="train")

    def be_workload(self, record: bool):
        from repro.core.segments import SegmentedWorkload
        sink = self.slices if record else []
        return SegmentedWorkload("be_train").device(
            lambda: _timed(self.train_op(), "train", "be_train", sink),
            label="train")

    # -- RT tasks --------------------------------------------------------
    def rt_workload(self, t: dict, record: bool):
        """Prefill of this release's prompt as one slice, then the
        sliced decode, each step timed; the first slice stamps the
        release's start, the decode's carry its first token."""
        from repro.core.segments import SegmentedWorkload
        eng, name = self.engines[t["name"]], t["name"]
        pool = self.prompts[name]
        sink = self.slices if record else []

        def rel():
            return self.current.get(name, {}) if record else {}

        def stamp(t0):
            rel().setdefault("start", t0)

        def prefill():
            r = rel()
            prompt = pool[r.get("k", 0) % pool.shape[0]]
            return _timed(eng.prefill_segment(prompt), "prefill", name,
                          sink, on_first=stamp)

        def decode():
            op = eng.decode_segment(t["new_tokens"],
                                    slice_tokens=t["slice_tokens"])
            r = rel()
            init = op.init

            def first_tok():
                carry = init()
                r["first"] = carry["tok"]
                return carry

            op.init = first_tok
            return _timed(op, "decode", name, sink, pos0=t["prompt"],
                          slice_tokens=t["slice_tokens"],
                          n_tokens=t["new_tokens"])

        return (SegmentedWorkload(name)
                .device(prefill, label="prefill")
                .device(decode, label="decode"))

    # -- the training check's program side -------------------------------
    def first_steps(self, n: int) -> dict:
        """Drive the BE job from the seed through its first ``n`` steps by
        the window's own call and feed: the loss of each, the first
        gradient as the optimizer holds it after step 1, and each leaf's
        change after step ``n``."""
        import jax
        op = self.train_op()
        losses, first = [], None
        b1 = self.opt["b1"]
        for i in range(n):
            op.step(None, 0)
            losses.append(float(self.train["loss"]))
            if i == 0:
                first = reference.leaf_norms(jax.tree.map(
                    lambda m: m / (1 - b1), self.train["opt"]["m"]))
        p0 = model.make_params(self.be_sizes, self.seed, self.device)
        change = reference.change_norms(self.train["params"], p0)
        del p0
        return {"losses": losses, "grad": first, "change": change}

    def free(self):
        """Drop every device array the program holds."""
        import jax
        for obj in (self.params, self.prompts, getattr(self, "feed", None),
                    self.train,
                    [e.cache for e in self.engines.values()]):
            for x in jax.tree.leaves(obj):
                if isinstance(x, jax.Array) and not x.is_deleted():
                    x.delete()
        self.engines.clear()
        self.outputs.clear()


def _profiles(cell: Cell) -> tuple:
    """Each RT task's and the BE job's measured profile, with the
    program's repetitions; the first repetition compiles and is
    dropped."""
    from repro.launch.preemptive import PROFILE_REPS
    rt = {t["name"]: cell.rt_workload(t, record=False).profile(
        reps=PROFILE_REPS) for t in cell.mix["rt"]}
    be = (cell.be_workload(record=False).profile(reps=PROFILE_REPS)
          if cell.train is not None else None)
    return rt, be


def _wcet_ms(prof, margin: float) -> float:
    host, dev = prof.segments_ms(margin)
    return sum(host) + sum(m + e for m, e in dev)


def epsilon_ms(rt: dict, be) -> float:
    """ε as ``launch/preemptive.py`` sets it: 1 ms of runlist update plus
    the margin-inflated longest profiled slice of any job."""
    from repro.launch.preemptive import MARGIN
    return 1.0 + MARGIN * max([p.max_slice_ms for p in rt.values()]
                              + ([be.max_slice_ms] if be else []))


def admit(client, cell: Cell, rt: dict, be, periods: dict, bodies: dict):
    """Submit every RT task at its fixed period and the BE job through
    the facade; returns {task: decision} (the BE job's under its own
    name)."""
    from repro.launch.preemptive import MARGIN
    from repro.sched import JobProfile
    out = {}
    for t in cell.mix["rt"]:
        prof = JobProfile.from_workload(
            rt[t["name"]], period_ms=periods[t["name"]],
            priority=t["priority"], margin=MARGIN)
        out[t["name"]] = client.submit(
            prof, body=bodies.get(t["name"]), n_iterations=10 ** 6)
    if be is None:
        return out
    # released back to back: its period is its own unmargined profile
    out["be_train"] = client.submit(
        JobProfile.from_workload(be, period_ms=_wcet_ms(be, 1.0),
                                 priority=0, best_effort=True,
                                 margin=MARGIN),
        body=bodies.get("be_train"), n_iterations=10 ** 6)
    return out


def window(cell: Cell, rt: dict, be, seconds: float, periods: dict,
           tracer=None) -> dict:
    """Admit at ``periods`` (task -> ms) and drive the window; return the
    evidence."""
    from repro.sched import connect
    mix = cell.mix
    eps = epsilon_ms(rt, be)
    offs = traffic.offsets_ms(mix, periods)
    rt_wl = {t["name"]: cell.rt_workload(t, record=True) for t in mix["rt"]}
    be_wl = cell.be_workload(record=True)
    releases, lock = [], threading.Lock()
    clock = {}

    def rt_body(t):
        name, wl = t["name"], rt_wl[t["name"]]

        def body(job, it):
            due = clock["t0"] + offs[name] / 1e3 + it * periods[name] / 1e3
            if due >= clock["end"]:
                return          # due after the window: not part of it
            rec = {"task": name, "k": it, "due": due}
            cell.current[name] = rec
            with lock:
                releases.append(rec)
            outs = wl.run(client.cluster, job)
            rec["done"] = time.monotonic()
            cell.outputs[(name, it)] = (rec.pop("first"), outs[1])
        return body

    def be_body(job, it):
        be_wl.run(client.cluster, job)

    client = connect(n_devices=1, policy="ioctl", wait_mode="suspend",
                     n_cpus=1, epsilon_ms=eps)
    try:
        bodies = {t["name"]: rt_body(t) for t in mix["rt"]}
        bodies["be_train"] = be_body
        decisions = admit(client, cell, rt, be, periods, bodies)
        out = _drive(client, cell, decisions, offs, periods, seconds,
                     clock, releases, tracer)
    finally:
        client.close(shutdown=True)
    out.update(epsilon_ms=eps, periods=periods, offsets_ms=offs,
               decisions=decisions)
    return out


def _drive(client, cell, decisions, offs, periods, seconds, clock,
           releases, tracer=None) -> dict:
    """Release the BE job, then each RT task at its phase; close the
    window after ``seconds``; wait until every release due in it has
    completed (at most the longest period and a minute more), then stop
    every job.  A thread that sleeps towards a release due after the
    window is not waited for: its body returns at once."""
    cluster = client.cluster
    t0 = time.monotonic() + 0.5
    clock.update(t0=t0, end=t0 + seconds)
    be = decisions["be_train"].job if "be_train" in decisions else None
    if be is not None:
        be.start(cluster, stop_after_s=None)
    rt_jobs = {n: d.job for n, d in decisions.items()
               if n != "be_train" and d.job is not None}
    due = {n: len(traffic.due_times(t0 + offs[n] / 1e3, periods[n] / 1e3,
                                    clock["end"])) for n in rt_jobs}
    starts = sorted((t0 + offs[n] / 1e3, n) for n in rt_jobs)
    horizon = clock["end"] + max(periods.values()) / 1e3 + 60.0

    def pending():
        done = {(r["task"], r["k"]) for r in releases if "done" in r}
        return [(n, k) for n, j in rt_jobs.items() for k in range(due[n])
                if (n, k) not in done and j.error is None
                and j._thread is not None and j._thread.is_alive()]

    for when, n in starts:
        delay = when - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rt_jobs[n].start(cluster, stop_after_s=clock["end"] - when)
    if tracer is not None:
        time.sleep(max(clock["end"] - TRACE_SECONDS - time.monotonic(), 0))
        tracer.start()
    time.sleep(max(clock["end"] - time.monotonic(), 0.0))
    while pending() and time.monotonic() < horizon:
        time.sleep(0.01)
    drained = time.monotonic()
    for j in rt_jobs.values():
        j.stop()
    if be is not None:
        be.stop()
        be.join(60.0)
    if tracer is not None:
        tracer.stop()
    return {"t0": t0, "end": clock["end"], "drained": drained,
            "horizon": horizon, "releases": list(releases),
            "jobs": {n: d.job for n, d in decisions.items()
                     if d.job is not None},
            "stuck": [r for r in releases if "done" not in r]}


# -- the comparison that decides `correct` ------------------------------

def _worst_leaf(prog: dict, ref: dict, ref_grad: dict) -> float:
    """The widest gap between the program's and the reference's norm of
    a leaf, over the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Leaves whose reference gradient is under
    a thousandth of the median leaf's move by round-off alone and are
    left out."""
    import statistics
    med_g = statistics.median(ref_grad.values())
    med = statistics.median(ref.values())
    keep = [k for k in ref if ref_grad[k] >= 1e-3 * med_g]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def train_readings(prog: dict, ref: dict) -> dict:
    return {
        "train_loss_gap": max(abs(a - b) / abs(b) for a, b in
                              zip(prog["losses"], ref["losses"])),
        "train_grad_gap": _worst_leaf(prog["grad"], ref["grad"],
                                      ref["grad"]),
        "train_update_gap": _worst_leaf(prog["change"], ref["change"],
                                        ref["grad"]),
    }


def reference_train(sz: dict, mix: dict, seed: int, device, steps: int,
                    quant=None, half: bool = False) -> dict:
    """The reference's three steps from the seed on the same rows, for the
    BE job's configuration ``sz``; with ``half`` the fault of a step that
    drops half of each batch."""
    be = mix["be"]
    feed = model.make_tokens(sz, seed, model.FEED,
                             {"rows": (be["feed_pool"], be["batch"],
                                       be["seq"] + 1)}, device)["rows"]
    rows = be["batch"] // 2 if half else be["batch"]
    batches = [(feed[i, :rows, :-1], feed[i, :rows, 1:])
               for i in range(steps)]
    p0 = model.make_params(sz, seed, device)
    losses, grad, p3 = reference.train(sz, mix["be"]["optimizer"], p0,
                                       batches, quant=quant)
    p0 = model.make_params(sz, seed, device)
    return {"losses": losses, "grad": grad,
            "change": reference.change_norms(p3, p0)}


def serve_readings(sz: dict, mix: dict, seed: int, device, served: dict,
                   controls: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over the sampled releases; with
    ``controls`` also the same gap for the token that the fp8 reference
    puts first."""
    import jax.numpy as jnp
    import numpy as np
    pool = mix["prompt_pool"]
    prompts = model.make_tokens(
        sz, seed, model.PROMPTS,
        {t["name"]: (pool, t["batch"], t["prompt"]) for t in mix["rt"]},
        device)
    params = model.make_params(sz, seed, device)
    worst, worst_ctrl, n_tok = 0.0, 0.0, 0
    for (name, k), (first, toks) in sorted(served.items()):
        served_seq = np.concatenate([first[:, None], toks], axis=1)
        n = served_seq.shape[1]
        seq = jnp.concatenate([prompts[name][k % pool],
                               jnp.asarray(served_seq[:, :-1])], axis=1)
        logits = reference.last_logits(sz, params, seq, n)
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, jnp.asarray(served_seq)[..., None],
                                  -1)[..., 0]
        worst = max(worst, float(jnp.max(best - got)))
        n_tok += served_seq.size
        if controls:
            low = reference.last_logits(sz, params, seq, n, quant="fp8")
            pick = jnp.argmax(low, -1)
            lg = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
            worst_ctrl = max(worst_ctrl, float(jnp.max(best - lg)))
    out = {"serve_gap": worst, "served_tokens_compared": n_tok}
    if controls:
        out["control.serve_gap"] = worst_ctrl
    return out


# -- one run ------------------------------------------------------------

def _rt_outcomes(mix: dict, ev: dict) -> dict:
    """Per task: the due releases' (response / deadline, met)."""
    done = {}
    for r in ev["releases"]:
        if "done" in r:
            done.setdefault(r["task"], {})[r["k"]] = r["done"]
    out = {}
    for t in mix["rt"]:
        name, p = t["name"], ev["periods"][t["name"]] / 1e3
        dues = traffic.due_times(ev["t0"] + ev["offsets_ms"][name] / 1e3,
                                 p, ev["end"])
        refused = ev["decisions"][name].job is None
        out[name] = traffic.outcomes(dues, {} if refused else
                                     done.get(name, {}), p, ev["horizon"])
    return out


def guarantee(ev: dict, mix: dict) -> dict:
    """What the system's guarantee says of answers, as
    ``check_preemptive`` states it, for every admitted RT task: no job
    error and no release left unfinished.  A task that admission refuses
    is no error of the guarantee: its releases are counted as failed."""
    return {
        "job_errors": sum(1 for j in ev["jobs"].values()
                          if j.error is not None),
        "unfinished": len(ev["stuck"]),
    }


def timing(ev: dict, mix: dict) -> dict:
    """The guarantee's timing, as ``check_preemptive`` states it: the
    largest MORT over the admitted WCRT of an RT task, and the longest
    slice of any job over ε; None where nothing was admitted or run.
    Reported, not judged: a release that comes late is late, not wrong,
    and on the host clock a machine that stands still for a second reads
    as a slice over ε."""
    jobs, dec = ev["jobs"], ev["decisions"]
    wcrt = {}
    for d in dec.values():
        wcrt.update(d.get("wcrt") or {})
    ratios = [jobs[n].stats.mort * 1e3 / wcrt[n]
              for n in (t["name"] for t in mix["rt"])
              if n in jobs and wcrt.get(n) and jobs[n].stats.mort]
    slices = [s for j in jobs.values() for s in j.stats.slice_times]
    return {
        "mort_over_wcrt": max(ratios) if ratios else None,
        "slice_over_eps": (max(slices) * 1e3 / ev["epsilon_ms"]
                           if slices else None),
    }


#: the traced part of a ``--trace 1`` window, its last seconds and the
#: drain: a trace of a whole window stalled train slices by seconds on a
#: v5e, and the trace stops only once no job runs
TRACE_SECONDS = 15.0


class WindowTrace:
    """The device and the benchmark's own host spans, traced from
    ``start()`` to ``stop()`` under the span ``bench.window``."""

    def __init__(self, log_dir: str):
        self.log_dir, self._span = log_dir, None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1      # the benchmark's own spans only
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


GUARANTEE_LIMITS = {"job_errors": 0, "unfinished": 0, "be_stalled": 0}

#: the numbers compared with the reference, each against its cell's limit
COMPARED = ("serve_gap", "train_loss_gap", "train_grad_gap",
            "train_update_gap")


def judge(readings: dict, limits: dict, gtee: dict) -> dict:
    """Each number that decides ``correct`` beside its limit: the
    comparison with the reference and the guarantee."""
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in COMPARED if k in limits}
    checks.update({k: {"value": v, "limit": GUARANTEE_LIMITS[k]}
                   for k, v in gtee.items()})
    return checks


def over(checks: dict) -> list:
    """The numbers of ``checks`` over their limits."""
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        checkout: Path = catalog.CHECKOUT, need_tpu: bool = True,
        controls: bool = False, log=print,
        started: float = PROCESS_START) -> dict:
    """One run of ``cell_name``.  Returns the result line's object;
    ``log`` receives the earlier lines."""
    import jax

    from .compile_meter import CompileMeter
    bench_dir = checkout / "bench"
    peaks = catalog.peaks(bench_dir)
    spec = catalog.cell(cell_name, checkout)
    devs = guard(spec["chips"], peaks, need_tpu=need_tpu)
    cache = compile_cache(checkout)
    meter = CompileMeter()
    dev = devs[0]
    peak = peaks.get(dev.device_kind)

    cell = Cell(spec, seed, dev, bench_dir)
    mix, sz, be_sz = cell.mix, cell.sizes, cell.be_sizes
    n_check = mix["be"]["checked_steps"] if "be" in mix else 0
    prog_train = cell.first_steps(n_check) if n_check else None
    rt, be = _profiles(cell)
    periods = {t["name"]: float(t["period_ms"]) for t in mix["rt"]}
    tracer, trace_dir = None, checkout / ".bench_cache" / "trace"
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = WindowTrace(str(trace_dir))
    snap = meter.snapshot()
    ev = window(cell, rt, be, seconds, periods, tracer)
    in_window = meter.since(snap)
    setup_s = ev["t0"] - started
    mem = dev.memory_stats() or {}
    outcomes = _rt_outcomes(mix, ev)
    gtee = guarantee(ev, mix)
    tim = timing(ev, mix)

    finished = {}
    for (name, k) in cell.outputs:
        finished.setdefault(name, []).append(k)
    picked = traffic.sample(mix, finished, seed)
    served = {(n, k): tuple(np.asarray(x) for x in cell.outputs[(n, k)])
              for n, ks in picked.items() for k in ks}
    r = Run(cell_name, sz, mix, peak or {}, be_sz, slices=list(cell.slices),
            releases=ev["releases"],
            job_stats={n: j.stats for n, j in ev["jobs"].items()},
            timing=tim)
    cell.free()
    del cell
    if trace:
        from . import trace as tr
        r.trace_events = tr.load(str(trace_dir))
        r.trace = tr.reduce(r.trace_events)

    be_tok = mix["be"]["batch"] * mix["be"]["seq"] if "be" in mix else 0
    steps = sum(1 for s in r.slices if s["kind"] == "train"
                and ev["t0"] <= s["end"] <= ev["end"])
    if be_tok:
        # check_preemptive's last condition: training progressed
        gtee["be_stalled"] = int(steps == 0)

    readings = serve_readings(sz, mix, seed, dev, served, controls)
    ref_train = (reference_train(be_sz, mix, seed, dev, n_check)
                 if n_check else None)
    if n_check:
        readings.update(train_readings(prog_train, ref_train))
    limits = catalog.limits(cell_name, bench_dir)
    checks = judge(readings, limits, gtee)
    correct = not over(checks)
    # each control's readings in the program's place, judged as the
    # program's are: the fp8 reference, and a step that drops half its
    # batch
    ctl = {}
    if controls:
        ctl["fp8"] = dict(readings, serve_gap=readings["control.serve_gap"])
    if controls and n_check:
        ctl["half_batch"] = dict(readings)
        for tag, kw in (("half_batch", {"half": True}),
                        ("fp8", {"quant": "fp8"})):
            got = train_readings(
                reference_train(be_sz, mix, seed, dev, n_check, **kw),
                ref_train)
            ctl[tag].update(got)
            readings.update({f"{tag}.{k}": v for k, v in got.items()})
    ctl_checks = {tag: judge(x, limits, gtee) for tag, x in ctl.items()}

    samples = [x for o in outcomes.values() for x in o]
    ratios = [x for x, _ in samples]
    failed = sum(1 for _, ok in samples if not ok)
    e2e = {"rt_resp_p95": (traffic.percentile(ratios, 95) if ratios
                           else None, "ratio"),
           "rt_resp_p50": (traffic.percentile(ratios, 50) if ratios
                           else None, "ratio"),
           "be_tokens_per_s": (steps * be_tok / seconds if be_tok
                               else None, "tokens/s"),
           "setup_s": (setup_s, "s")}
    if trace:
        metrics = {}
        for m in catalog.metrics_of(cell_name, "per_layer", checkout):
            v = catalog.metric_reader(m["name"], bench_dir)(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]][0],
                               "unit": m["unit"]}
                   for m in catalog.metrics_of(cell_name, "end_to_end",
                                               checkout)
                   if e2e.get(m["name"], (None,))[0] is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    if trace and r.trace:
        device.update(busy_s=r.trace["busy_s"], window_s=r.trace["window_s"])

    log(json.dumps({"info": {
        "compile_cache": cache, "setup_s": setup_s,
        "in_window": in_window, "compile_total_s": meter.seconds,
        "programs_total": meter.programs,
        "cache_hits_total": meter.cache_hits,
        "epsilon_ms": ev["epsilon_ms"], "timing": tim,
        "periods_ms": periods,
        "offsets_ms": ev["offsets_ms"],
        "wcrt_ms": {n: d.get("wcrt", {}).get(n) for n, d in
                    ev["decisions"].items()},
        "refused": [n for n, d in ev["decisions"].items() if d.job is None],
        "profiled_ms": {n: _wcet_ms(p, 1.0) for n, p in rt.items()},
        "profiled_be_ms": _wcet_ms(be, 1.0) if be else None,
        "releases_due": {n: len(o) for n, o in outcomes.items()},
        "releases_met": {n: sum(ok for _, ok in o)
                         for n, o in outcomes.items()},
        "be_steps_in_window": steps,
        "drain_s": ev["drained"] - ev["end"],
        "longest_slices": [[x["kind"], x["task"], x["start"] - ev["t0"],
                            (x["end"] - x["start"]) * 1e3] for x in sorted(
            r.slices, key=lambda x: x["start"] - x["end"])[:5]],
        "jobstats_max_ms": {n: max(st.slice_times) * 1e3 for n, st in
                            r.job_stats.items() if st.slice_times},
        "readings": readings, "e2e": {k: v[0] for k, v in e2e.items()},
        "train_losses": ({"program": prog_train["losses"],
                          "reference": ref_train["losses"]}
                         if n_check else None),
    }}))
    out = {"correct": correct, "attempted": len(samples), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and r.trace:
        out["breakdown"] = {"device_ops": r.trace["device_ops"],
                            "idle_gaps": r.trace["idle_gaps"]}
    if controls:
        out["controls"] = {tag: {"correct": not over(c), "over": over(c)}
                           for tag, c in ctl_checks.items()}
    out["checks"] = checks
    return out



def sweep(cell_name: str, seed: int, seconds: float, *,
          checkout: Path = catalog.CHECKOUT, log=print) -> dict:
    """The knee of the cell's mix on this chip, found once.  Each task's
    base period is its analysed response (its WCRT with the other tasks
    at periods too long to interfere more than once), priorities
    rate-monotonic in it; all periods are one scale times their base.
    The knee is the smallest scale at which admission takes all three
    tasks and a window of ``seconds`` has no miss.  The cell runs at 4/5
    of the knee's rate, 1.25 times its scale."""
    from repro.launch.preemptive import MARGIN
    from repro.sched import JobProfile, connect
    bench_dir = checkout / "bench"
    spec = catalog.cell(cell_name, checkout)
    devs = guard(spec["chips"], catalog.peaks(bench_dir))
    compile_cache(checkout)
    cell = Cell(spec, seed, devs[0], bench_dir)
    rt, be = _profiles(cell)
    eps = epsilon_ms(rt, be)
    names = list(rt)

    def admit_at(periods: dict, prio: dict):
        client = connect(n_devices=1, policy="ioctl", wait_mode="suspend",
                         n_cpus=1, epsilon_ms=eps)
        try:
            decs = [client.submit(JobProfile.from_workload(
                rt[n], period_ms=periods[n], priority=prio[n],
                margin=MARGIN), body=lambda j, i: None)
                for n in sorted(names, key=lambda n: -prio[n])]
            return all(d.accepted for d in decs), decs[-1].get("wcrt", {})
        finally:
            client.close(shutdown=True)

    # base = analysed response with the others far apart; priorities
    # follow the base until the order settles
    base = {n: _wcet_ms(rt[n], MARGIN) for n in names}
    for _ in range(4):
        order = sorted(names, key=base.get)
        prio = {n: 50 - 10 * i for i, n in enumerate(order)}
        far = {n: 1e3 * max(base.values()) * len(names) for n in names}
        ok, wcrt = admit_at(far, prio)
        if not ok:
            raise RuntimeError("admission refuses the mix at any period")
        base = {n: wcrt[n] for n in names}
        if sorted(names, key=base.get) == order:
            break
    for t in cell.mix["rt"]:
        t["priority"] = prio[t["name"]]
    s, adm = 4.0, []
    while s > 1.0 and admit_at({n: s * base[n] for n in names}, prio)[0]:
        adm.append(s)
        s *= 0.95
    if not adm:
        raise RuntimeError("admission refuses the mix at 4x its analysed "
                           "responses")
    s_adm = min(adm)
    runs, knee = [], None
    for f in (1.0, 1.1, 1.25, 1.5, 2.0):
        periods = {n: f * s_adm * base[n] for n in base}
        ev = window(cell, rt, be, seconds, periods)
        oc = _rt_outcomes(cell.mix, ev)
        miss = sum(1 for o in oc.values() for _, ok in o if not ok)
        ratios = [x for o in oc.values() for x, _ in o]
        runs.append({"scale": f * s_adm, "periods_ms": periods,
                     "due": len(ratios), "missed": miss,
                     "p95": traffic.percentile(ratios, 95),
                     "guarantee": guarantee(ev, cell.mix),
                     "timing": timing(ev, cell.mix)})
        log(json.dumps({"sweep_run": runs[-1]}))
        if miss == 0:
            knee = f * s_adm
            break
    out = {"analysed_base_ms": base, "epsilon_ms": eps,
           "profiled_ms": {n: _wcet_ms(rt[n], 1.0) for n in names},
           "priorities": prio, "smallest_admitted_scale": s_adm,
           "runs": runs, "knee_scale": knee}
    if knee is not None:
        out["cell_scale"] = 1.25 * knee
        out["cell_periods_ms"] = {n: 1.25 * knee * base[n] for n in base}
    return out
