"""Journaled job store (sched/store.py) + the structured
AdmissionDecision (DESIGN.md §9): journal/replay round-trips, atomic
snapshot compaction (including the crash window between the two
replaces), decision compatibility with historical bare-dict call sites,
admission state export → rebuild with decision-conformance, and the
checkpointer's shutdown-drain / gc-vs-restore guards the durable path
leans on."""
import json
import os
import subprocess
import sys
import threading

import pytest

from repro.sched import (AdmissionController, AdmissionDecision,
                         CompactionPolicy, JobProfile, JobStore,
                         RecoveryConformanceError, decisions_match)


def prof(name, prio, device=0, exec_ms=4.0, period_ms=50.0, cpu=0,
         best_effort=False):
    return JobProfile(name, host_segments_ms=[1.0],
                      device_segments_ms=[(0.5, exec_ms)],
                      period_ms=period_ms, priority=prio, cpu=cpu,
                      best_effort=best_effort, device=device)


# ---------------------------------------------------------------------------
# AdmissionDecision: structured result, historical dict face intact
# ---------------------------------------------------------------------------

def test_decision_bool_and_dict_faces_agree():
    acc = AdmissionDecision.accept("default", {"a": 12.5})
    ref = AdmissionDecision.refuse("rta-reject", wcrt={"a": None})
    assert acc and not ref                      # __bool__
    assert acc["admitted"] and not ref["admitted"]   # historical face
    assert acc.reason == "accepted" and ref.reason == "rta-reject"
    assert acc.wcrt == {"a": 12.5} == acc["wcrt"]
    # equality with a plain dict still holds (tests compare verbatim)
    assert acc == {"admitted": True, "reason": "accepted",
                   "via": "default", "wcrt": {"a": 12.5}}


def test_decision_validates_reason_consistency():
    with pytest.raises(ValueError, match="unknown reason"):
        AdmissionDecision(admitted=True, reason="because")
    with pytest.raises(ValueError, match="contradicts"):
        AdmissionDecision(admitted=True, reason="rta-reject")


def test_decision_journal_form_strips_live_job():
    dec = AdmissionDecision.accept("default", {"a": 1.0})
    bound = dec.bound(2, object())
    jf = bound.journal_form()
    assert jf["device"] == 2 and "job" not in jf
    json.dumps(jf)  # journalable verbatim


def test_try_admit_reason_codes():
    ctl = AdmissionController(policy="ioctl", n_devices=1)
    assert ctl.try_admit(prof("ok", 1)).reason == "accepted"
    assert (ctl.try_admit(prof("bad-dev", 2, device=5)).reason
            == "validation-refused")
    assert (ctl.try_admit(prof("ok", 2)).reason  # duplicate name
            == "validation-refused")
    hot = ctl.try_admit(prof("hot", 2, exec_ms=80.0, period_ms=50.0))
    assert hot.reason == "headroom-fast-reject" and hot.wcrt == {}
    tight = ctl.try_admit(prof("tight", 2, exec_ms=44.0,
                               period_ms=50.0))
    assert tight.reason == "rta-reject" and tight.wcrt  # evidence kept


def test_decisions_match_tolerance_and_inf():
    a = {"admitted": True, "reason": "accepted", "via": "default",
         "wcrt": {"x": 10.0}}
    assert decisions_match(a, dict(a, wcrt={"x": 10.0 + 1e-9}))
    assert not decisions_match(a, dict(a, wcrt={"x": 10.1}))
    assert not decisions_match(a, dict(a, via="audsley"))
    inf = dict(a, admitted=False, reason="rta-reject", via=None,
               wcrt={"x": None})
    assert decisions_match(inf, dict(inf, wcrt={"x": float("inf")}))


# ---------------------------------------------------------------------------
# journal / replay
# ---------------------------------------------------------------------------

def test_journal_replay_round_trip(tmp_path):
    with JobStore(str(tmp_path)) as st:
        ctl = AdmissionController(policy="ioctl", n_devices=2)
        st.record_config(ctl.export_config(), {"n_devices": 2})
        p = prof("a", 1)
        st.record_decision(p, ctl.try_admit(p), device=0,
                           workload={"name": "demo.spin", "kwargs": {}},
                           n_iterations=3)
        st.record_carry("a", 0, 2)
        st.record_carry("a", 0, 4)
        st.record_iteration_done("a", 0)
        st.record_carry("a", 1, 1)
        refused = prof("a", 2)           # duplicate -> refusal audit
        st.record_decision(refused, ctl.try_admit(refused))
    state = JobStore(str(tmp_path)).load()
    assert state.config["n_devices"] == 2
    rec = state.jobs["a"]
    assert rec.device == 0 and rec.n_iterations == 3
    assert rec.done_iterations == 1           # iter 0 finalized
    assert rec.carry == {"iteration": 1, "slice": 1}
    assert len(state.refusals) == 1
    assert state.refusals[0]["decision"]["reason"] == "validation-refused"


def test_release_removes_job_from_state(tmp_path):
    with JobStore(str(tmp_path)) as st:
        ctl = AdmissionController(policy="ioctl")
        p = prof("a", 1)
        st.record_decision(p, ctl.try_admit(p), device=0)
        st.record_release("a")
        assert st.load().jobs == {}


def test_torn_final_journal_line_is_skipped(tmp_path):
    st = JobStore(str(tmp_path))
    ctl = AdmissionController(policy="ioctl")
    p = prof("a", 1)
    st.record_decision(p, ctl.try_admit(p), device=0)
    st.close()
    with open(os.path.join(str(tmp_path), "journal.jsonl"), "a") as f:
        f.write('{"rec": "carry", "job": "a", "iter')   # crash mid-append
    state = JobStore(str(tmp_path)).load()
    assert "a" in state.jobs and state.jobs["a"].carry is None


def test_unknown_record_kinds_are_skipped(tmp_path):
    st = JobStore(str(tmp_path))
    st._append({"rec": "future-audit-kind", "x": 1})
    assert st.load().jobs == {}


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------

def test_compaction_preserves_state_and_truncates_journal(tmp_path):
    st = JobStore(str(tmp_path))
    ctl = AdmissionController(policy="ioctl", n_devices=1)
    st.record_config(ctl.export_config(), {"n_devices": 1})
    for name in ("a", "b"):
        p = prof(name, {"a": 1, "b": 2}[name])
        st.record_decision(p, ctl.try_admit(p), device=0)
    st.record_carry("a", 0, 3)
    st.compact()
    assert os.path.getsize(os.path.join(str(tmp_path),
                                        "journal.jsonl")) == 0
    state = st.load()
    assert sorted(state.jobs) == ["a", "b"]
    assert state.jobs["a"].carry == {"iteration": 0, "slice": 3}
    assert state.config is not None
    # appends keep working after compaction, on top of the snapshot
    st.record_release("a")
    assert sorted(st.load().jobs) == ["b"]
    st.close()


def test_compaction_crash_window_double_apply_is_idempotent(tmp_path):
    """Snapshot replaced but journal not yet truncated (the crash window
    between compact()'s two atomic replaces): replay applies every
    journal record on top of a snapshot that already contains it."""
    st = JobStore(str(tmp_path))
    ctl = AdmissionController(policy="ioctl")
    p = prof("a", 1)
    st.record_decision(p, ctl.try_admit(p), device=0)
    st.record_carry("a", 0, 2)
    before = st.load()
    # simulate: write the snapshot exactly as compact() would, but leave
    # the journal in place
    snap = {"v": 1, "config": before.config, "cluster": before.cluster,
            "jobs": {n: r.to_json() for n, r in before.jobs.items()}}
    with open(os.path.join(str(tmp_path), "snapshot.json"), "w") as f:
        json.dump(snap, f)
    after = JobStore(str(tmp_path)).load()
    assert after.jobs["a"].to_json() == before.jobs["a"].to_json()
    st.close()


def test_compact_concurrent_appends_lose_nothing(tmp_path):
    """Compaction racing a writer must not drop records: an earlier
    ``compact()`` folded the journal *outside* the store lock, so a
    decision appended between the fold and the journal truncation
    silently vanished.  Hammer that window: a thread appends admitted
    decisions while the main thread compacts in a tight loop — every
    appended job must survive into the folded state."""
    st = JobStore(str(tmp_path), sync=False)
    n = 300
    dec = {"admitted": True, "reason": "accepted", "via": "default",
           "wcrt": {}}

    def spam():
        for i in range(n):
            st.record_decision(prof(f"j{i}", 1), dec, device=0)

    t = threading.Thread(target=spam)
    t.start()
    while t.is_alive():
        st.compact()
    t.join()
    st.compact()
    state = st.load()
    assert sorted(state.jobs) == sorted(f"j{i}" for i in range(n)), \
        f"lost {n - len(state.jobs)} records to the compaction race"
    st.close()


def test_auto_compaction_policy_triggers(tmp_path):
    pol = CompactionPolicy(max_bytes=None, max_records=10)
    assert pol.due(0, 10, 0.0) and not pol.due(10**9, 9, 10**9)
    st = JobStore(str(tmp_path), sync=False, auto_compact=pol)
    dec = {"admitted": True, "reason": "accepted", "via": "default",
           "wcrt": {}}
    for i in range(25):
        st.record_decision(prof(f"j{i}", 1), dec, device=0)
    assert st.compactions >= 2
    with open(os.path.join(str(tmp_path), "journal.jsonl")) as f:
        assert sum(1 for ln in f if ln.strip()) < 10
    assert sorted(st.load().jobs) == sorted(f"j{i}" for i in range(25))
    st.close()


def test_failover_fold_displaced_until_settled(tmp_path):
    """A ``failover`` record moves the failed device's jobs onto the
    displaced ledger; they stay *unaccounted* until a follow-up
    decision (re-admission or refusal) settles them — the no-silent-
    job-loss audit the chaos suite replays."""
    st = JobStore(str(tmp_path), sync=False)
    ctl = AdmissionController(policy="ioctl", n_devices=2)
    for p in (prof("a", 1, device=0), prof("b", 2, device=1)):
        st.record_decision(p, ctl.try_admit(p), device=p.device)
    st.record_failover(0, epoch=1, reason="hw")
    mid = st.load()
    assert mid.epoch == 1 and mid.failed_devices == {0}
    assert mid.unaccounted() == ["a"] and sorted(mid.jobs) == ["b"]
    # settle "a": re-admitted on device 1 in the new epoch (the live
    # fail-over path re-derives the whole admission state, so the
    # displaced profile no longer charges the controller)
    ctl.release("a")
    a1 = prof("a", 1, device=1)
    st.record_decision(a1, ctl.try_admit(a1), device=1, epoch=1)
    state = st.load()
    assert state.unaccounted() == []
    assert list(state.jobs) == ["b", "a"]    # decision order preserved
    assert state.jobs["a"].device == 1
    # an explicit refusal also settles (accounted, not silently lost)
    st.record_failover(1, epoch=2, reason="hw")
    st.record_decision(prof("b", 2, device=1),
                       AdmissionDecision.refuse(
                           "validation-refused",
                           error="no surviving device"), epoch=2)
    end = st.load()
    # "a" lived on device 1 too — it stays *unaccounted* until settled,
    # which is exactly what the audit must flag
    assert end.unaccounted() == ["a"] and "b" not in end.jobs
    st.record_decision(prof("a", 1, device=0),
                       AdmissionDecision.refuse(
                           "validation-refused",
                           error="no surviving device"), epoch=2)
    assert st.load().unaccounted() == []
    # compaction round-trips the fault-containment state
    st.compact()
    snap = st.load()
    assert snap.epoch == 2 and snap.failed_devices == {0, 1}
    st.close()


def test_shed_fold_and_resume_decision(tmp_path):
    st = JobStore(str(tmp_path), sync=False)
    ctl = AdmissionController(policy="ioctl", n_devices=1)
    be = prof("be", 0, best_effort=True)
    st.record_decision(be, ctl.try_admit(be), device=0)
    st.record_carry("be", 0, 4)
    st.record_shed("be", "overload")
    mid = st.load()
    assert "be" not in mid.jobs and "be" in mid.shed
    assert mid.shed["be"].carry == {"iteration": 0, "slice": 4}
    ctl.release("be")
    st.record_decision(be, ctl.try_admit(be), device=0)   # resume
    state = st.load()
    assert "be" in state.jobs and state.shed == {}
    st.close()


def test_request_id_dedup_table_folds(tmp_path):
    st = JobStore(str(tmp_path), sync=False)
    ctl = AdmissionController(policy="ioctl", n_devices=1)
    p = prof("a", 1)
    st.record_decision(p, ctl.try_admit(p), device=0, request_id="r-1")
    st.compact()                     # the table survives compaction
    state = st.load()
    assert state.requests["r-1"]["job"] == "a"
    assert state.requests["r-1"]["admitted"] is True
    st.close()


def test_appends_are_thread_safe(tmp_path):
    st = JobStore(str(tmp_path), sync=False)

    def spam(k):
        for i in range(50):
            st.record_carry(f"job{k}", 0, i)

    threads = [threading.Thread(target=spam, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with open(os.path.join(str(tmp_path), "journal.jsonl")) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 200          # no torn interleaved writes
    st.close()


# ---------------------------------------------------------------------------
# export / rebuild (recovery decision-conformance)
# ---------------------------------------------------------------------------

def _journal_two_jobs(tmp_path):
    st = JobStore(str(tmp_path))
    ctl = AdmissionController(policy="ioctl", n_devices=2)
    st.record_config(ctl.export_config(), {"n_devices": 2})
    for p in (prof("a", 1, device=0), prof("b", 2, device=1),
              prof("be", 0, best_effort=True)):
        st.record_decision(p, ctl.try_admit(p), device=p.device)
    st.close()
    return ctl


def test_rebuild_reproduces_admission_state(tmp_path):
    orig = _journal_two_jobs(tmp_path)
    state = JobStore(str(tmp_path)).load()
    ctl = AdmissionController.rebuild(state.config,
                                      state.admission_entries())
    assert [p.name for p in ctl.admitted] == [p.name
                                              for p in orig.admitted]
    assert ctl.export_config() == orig.export_config()
    # and the rebuilt controller prices new admissions identically
    nxt = prof("c", 3, device=0)
    assert decisions_match(orig.try_admit(nxt), ctl.try_admit(nxt))


def test_rebuild_conformance_mismatch_raises(tmp_path):
    _journal_two_jobs(tmp_path)
    state = JobStore(str(tmp_path)).load()
    entries = state.admission_entries()
    entries[0]["decision"] = dict(entries[0]["decision"],
                                  wcrt={"a": 999.0})   # drifted evidence
    with pytest.raises(RecoveryConformanceError, match="reproduce"):
        AdmissionController.rebuild(state.config, entries)
    # conform=False skips the identity check (debug escape hatch)
    ctl = AdmissionController.rebuild(state.config, entries,
                                      conform=False)
    assert len(ctl.admitted) == 3


def test_rebuild_refusal_on_readmission_raises(tmp_path):
    _journal_two_jobs(tmp_path)
    state = JobStore(str(tmp_path)).load()
    cfg = dict(state.config, headroom=1e-6)   # platform model drifted
    with pytest.raises(RecoveryConformanceError, match="refused"):
        AdmissionController.rebuild(cfg, state.admission_entries())


# ---------------------------------------------------------------------------
# checkpointer: shutdown drain + gc-vs-restore guard (satellite 3)
# ---------------------------------------------------------------------------

def test_atexit_drains_inflight_async_save(tmp_path):
    """An AsyncCheckpointer save in flight at interpreter exit must be
    drained, not killed with the daemon worker thread."""
    code = """
import sys
sys.path.insert(0, {src!r})
import numpy as np
from repro.sched import AsyncCheckpointer

class SlowArr(np.ndarray):
    pass

ckpt = AsyncCheckpointer({d!r}, keep=3)
import repro.sched.checkpointer as cp
orig = cp.save
def slow_save(ckpt_dir, step, tree):
    import time
    time.sleep(0.8)
    return orig(ckpt_dir, step, tree)
cp.save = slow_save
ckpt.save(1, {{"w": np.arange(4)}})
# exit immediately: without the atexit drain the worker dies mid-sleep
"""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, REPRO_PALLAS="interpret")
    subprocess.run(
        [sys.executable, "-c",
         code.format(src=os.path.abspath(src), d=str(tmp_path))],
        check=True, env=env, timeout=120)
    from repro.sched import latest_step
    assert latest_step(str(tmp_path)) == 1


def test_gc_skips_step_held_by_concurrent_restore(tmp_path):
    import numpy as np

    from repro.sched import AsyncCheckpointer, restore, save
    from repro.sched.checkpointer import _reading

    for s in range(5):
        save(str(tmp_path), s, {"w": np.full(3, s)})
    ckpt = AsyncCheckpointer(str(tmp_path), keep=2)
    with _reading(str(tmp_path), 0):
        ckpt._gc()
        # step 0 is being read: exempt this pass
        assert os.path.isdir(os.path.join(str(tmp_path), "step_00000000"))
        out = restore(str(tmp_path), {"w": np.zeros(3)}, step=0)
        assert out["w"].tolist() == [0, 0, 0]
    ckpt._gc()                       # reader gone: next pass collects it
    assert not os.path.isdir(os.path.join(str(tmp_path), "step_00000000"))


def test_resume_takes_the_journaled_carry_not_a_newer_snapshot(tmp_path):
    """A kill between a carry snapshot and its journal record leaves a
    snapshot that no record acknowledges: recovery resumes at the
    journaled slice, from that slice's snapshot."""
    import contextlib
    from types import SimpleNamespace

    from repro.sched import checkpointer
    from repro.sched.workloads import get_workload, make_body

    spec = {"name": "demo.count", "kwargs": {"total": 64, "per_slice": 8}}
    store = JobStore(str(tmp_path))
    op = get_workload("demo.count")(**spec["kwargs"])
    carry = op.init()
    for i in range(5):
        carry = op.step(carry, i)
        checkpointer.save_carry(store.carry_dir("j"), "j", i + 1, carry)
        if i < 4:                       # slice 5's record never landed
            store.record_carry("j", 0, i + 1)
    seen = {}

    class Executor:
        @contextlib.contextmanager
        def device_segment(self, job):
            yield

        def run_sliced(self, job, op, *, carry, start, **_):
            seen.update(start=start, sum=int(carry["sum"]))

    body = make_body(Executor(), "j", spec, store=store,
                     resume={"iteration": 0, "slice": 4})
    body(SimpleNamespace(device=None), 0)
    assert seen == {"start": 4, "sum": sum(range(1, 33))}
