"""Static analysis + sanitizer (ISSUE 10).

Covers: the access-mode checker on seeded mis-declarations and on the
shipped benchsuite declarations (zero false positives), the happens-before
verifier on green plans and on seeded edge-drop/liveness/structure
mutations (greedy and planopt-rewritten), the live-DAG window verifier,
the ``sanitize=True`` runtime mode (race detection on both executors,
write-through-const canary, bit-identical when off), the structured
``MemoryManager.verify`` drift report + daemon monitor surfacing, and the
journal auditor (every seeded mutation flagged, clean journals pass).
"""
import dataclasses
import json
import os
import tempfile

import numpy as np
import pytest

import repro.api as gr
from repro.analysis import (SanitizerError, Sanitizer, analyze_function,
                            verify_elements, verify_plan, verify_scheduler,
                            audit_journal, PlanVerificationError)
from repro.core import const, inout, make_scheduler, out
from repro.core.element import AccessMode, ComputationalElement, ElementKind
from repro.core.memory import MemoryDriftError


# ----------------------------------------------------------------------
# Access-mode checker: seeded mis-declarations
# ----------------------------------------------------------------------

def _issues(gf):
    report = analyze_function(gf)
    assert report.skipped is None, f"unexpectedly skipped: {report.skipped}"
    return report, report.issues


def test_mode_checker_flags_out_that_reads_its_input():
    bad = gr.function(lambda x, y: (x + y,), modes=("const", "out"),
                      name="bad_out_reads")
    _report, issues = _issues(bad)
    assert any(i.kind == "under" and i.arg == 1 for i in issues), issues


def test_mode_checker_flags_inout_never_read():
    bad = gr.function(lambda x, y: (x * 2.0,), modes=("const", "inout"),
                      name="bad_inout_dead")
    _report, issues = _issues(bad)
    assert any(i.kind == "over" and i.arg == 1 for i in issues), issues


def test_mode_checker_flags_more_outputs_than_writable_args():
    bad = gr.function(lambda x: (x * 2.0, x + 1.0), modes=("const",),
                      name="bad_extra_out")
    _report, issues = _issues(bad)
    assert any(i.kind == "under" for i in issues), issues


def test_mode_checker_flags_declared_write_that_never_happens():
    bad = gr.function(lambda x, y, z: (x * 2.0,),
                      modes=("const", "out", "out"), name="bad_missing_out")
    _report, issues = _issues(bad)
    assert any(i.kind == "over" for i in issues), issues


def test_mode_checker_flags_inplace_const_mutation():
    def kernel(x, y):
        if isinstance(x, np.ndarray):   # concrete probe only; pure on trace
            x += 1.0
        return (x * 2.0,)

    bad = gr.function(kernel, modes=("const", "out"), name="bad_const_mut")
    _report, issues = _issues(bad)
    assert any(i.kind == "under" and i.declared == "const"
               for i in issues), issues


def test_mode_checker_clean_declaration_and_shape_only_use():
    good = gr.function(lambda x, y: (x * 2.0,), modes=("const", "out"),
                       name="good_square")
    report, issues = _issues(good)
    assert not issues and report.reads == (True, False)
    # Using an out placeholder's *shape* (not its value) is legal.
    import jax.numpy as jnp
    shapeonly = gr.function(lambda x, y: (jnp.zeros_like(y) + x,),
                            modes=("const", "out"), name="good_shape_only")
    _report, issues = _issues(shapeonly)
    assert not issues, issues


def test_mode_checker_skips_unanalyzable_never_errors():
    sim_only = gr.function(None, modes=("inout",), name="bad_sim_only")
    report = analyze_function(sim_only)
    assert report.skipped and not report.issues


def test_mode_checker_zero_false_positives_on_shipped_declarations():
    import importlib

    from repro.analysis.cli import _LINT_MODULES
    from repro.analysis.modes import lint_functions
    for mod in _LINT_MODULES:
        importlib.import_module(mod)
    reports = [r for r in lint_functions()
               if not r.function.startswith(("bad_", "good_"))]
    assert len(reports) >= 20, "lint swept almost nothing"
    bad = [str(i) for r in reports for i in r.issues]
    assert not bad, bad


# ----------------------------------------------------------------------
# Plan verifier: green plans + seeded mutations
# ----------------------------------------------------------------------

def _vec_episode(s, tag=""):
    n = 256
    x1 = s.array(np.ones(n, np.float32), name=f"x1{tag}")
    x2 = s.array(np.full(n, 2.0, np.float32), name=f"x2{tag}")
    y1 = s.array(shape=(n,), dtype=np.float32, name=f"y1{tag}")
    y2 = s.array(shape=(n,), dtype=np.float32, name=f"y2{tag}")
    z = s.array(shape=(n,), dtype=np.float32, name=f"z{tag}")
    s.launch(None, [const(x1), out(y1)], name="SQ1", cost_s=1e-4)
    s.launch(None, [const(x2), out(y2)], name="SQ2", cost_s=1e-4)
    s.launch(None, [const(y1), const(y2), out(z)], name="RED", cost_s=1e-4)


def _captured_plan(**kw):
    s = make_scheduler("parallel", simulate=True, **kw)
    with s.capture("vec"):
        _vec_episode(s)
    plan = s.plan_cache.all_plans()[0]
    s.sync()
    s.shutdown()
    return plan


def _mutate_element(plan, idx, **changes):
    els = list(plan.elements)
    els[idx] = dataclasses.replace(els[idx], **changes)
    return dataclasses.replace(plan, elements=tuple(els))


def test_plan_verifier_green_on_captured_plan():
    plan = _captured_plan()
    assert verify_plan(plan) == []
    assert len(plan.elements) >= 5      # transfers + 3 kernels


def test_plan_verifier_flags_dropped_wait_event():
    plan = _captured_plan()
    flagged = 0
    for i, pe in enumerate(plan.elements):
        for ev in pe.wait_events:
            mut = _mutate_element(
                plan, i,
                wait_events=tuple(e for e in pe.wait_events if e != ev))
            vs = verify_plan(mut)
            if vs:
                flagged += 1
                assert all(v.kind in ("parent-order", "race") for v in vs)
    assert flagged >= 1, "no wait_event drop was ever flagged"


def test_plan_verifier_flags_unordered_conflict_as_race():
    plan = _captured_plan()
    # Drop an enforced cross-lane edge *and* its parent claim: the pair is
    # then genuinely unordered and must surface as a race, not merely as a
    # parent-order inconsistency.
    for i, pe in enumerate(plan.elements):
        for ev in pe.wait_events:
            mut = _mutate_element(
                plan, i,
                wait_events=tuple(e for e in pe.wait_events if e != ev),
                parents=tuple(p for p in pe.parents if p != ev))
            races = [v for v in verify_plan(mut) if v.kind == "race"]
            if races:
                assert any(k in str(races[0])
                           for k in ("RAW", "WAR", "WAW"))
                return
    pytest.fail("no dropped edge produced an unordered conflicting pair")


def test_plan_verifier_flags_planopt_rewritten_plan():
    from repro.benchsuite import build_task_parallel
    s = make_scheduler("parallel", simulate=True, num_devices=2,
                       placement="round-robin", plan_optimize=True)
    with s.capture("tp"):
        build_task_parallel(s, branches=3, chain=3, n=1 << 10)
    plan = s.plan_cache.all_plans()[0]
    s.sync()
    s.shutdown()
    assert plan.optimized, "planopt never rewrote the captured plan"
    assert verify_plan(plan) == []
    for i, pe in enumerate(plan.elements):
        for ev in pe.wait_events:
            mut = _mutate_element(
                plan, i,
                wait_events=tuple(e for e in pe.wait_events if e != ev),
                parents=tuple(p for p in pe.parents if p != ev))
            if verify_plan(mut):
                return
    pytest.fail("no edge drop on the optimized plan was flagged")


def test_plan_verifier_flags_index_scramble_as_structure():
    plan = _captured_plan()
    mut = _mutate_element(plan, 1, index=5)
    vs = verify_plan(mut)
    assert vs and vs[0].kind == "structure"


def test_plan_verifier_flags_read_of_evicted_slot():
    # Budget fits ~3.5 arrays; reusing 3 inputs across two passes forces
    # evictions and reloads inside one captured episode.
    n = 1 << 10
    s = make_scheduler("parallel", simulate=True,
                       memory_budget=int(n * 4 * 3.5))
    xs = [s.array(np.ones(n, np.float32), name=f"x{i}") for i in range(3)]
    with s.capture("reuse"):
        for rep in range(2):
            for i, x in enumerate(xs):
                y = s.array(shape=(n,), dtype=np.float32,
                            name=f"y{rep}_{i}")
                s.launch(None, [const(x), out(y)], name=f"K{rep}_{i}",
                         cost_s=1e-4)
    plan = s.plan_cache.all_plans()[0]
    s.sync()
    s.shutdown()
    assert verify_plan(plan) == []
    evict_idx = [i for i, pe in enumerate(plan.elements)
                 if pe.kind is ElementKind.EVICT]
    assert evict_idx, "budgeted capture recorded no evictions"
    placing = (ElementKind.TRANSFER, ElementKind.RELOAD, ElementKind.D2D)
    for i in evict_idx:
        for slot, _m in plan.elements[i].arg_slots:
            for j in range(i + 1, len(plan.elements)):
                pe = plan.elements[j]
                if pe.kind in placing and any(sl == slot
                                              for sl, _ in pe.arg_slots):
                    # Neutralize the element that re-materializes the slot:
                    # every later read now sees evicted data.
                    mut = _mutate_element(plan, j, arg_slots=tuple(
                        (sl, m) for sl, m in pe.arg_slots if sl != slot))
                    vs = verify_plan(mut)
                    if any(v.kind == "liveness" for v in vs):
                        return
    pytest.fail("suppressing a reload never produced a liveness violation")


# ----------------------------------------------------------------------
# Live-DAG window verifier
# ----------------------------------------------------------------------

def test_live_window_green_then_dropped_parent_flagged():
    s = make_scheduler("parallel", simulate=True)
    _vec_episode(s)
    assert verify_scheduler(s) == []
    window = list(s._elements)
    s.sync()
    s.shutdown()
    red = next(e for e in window if e.name == "RED")
    sq1 = next(e for e in window if e.name == "SQ1")
    assert sq1 in red.parents
    red.parents = [p for p in red.parents if p is not sq1]
    vs = verify_elements(window)
    assert any(v.kind == "race" and "RAW" in v.message for v in vs), vs


def test_live_window_host_barrier_and_serial_total_order():
    # Serial policy: every launch is host-blocking, the window is totally
    # ordered by construction and must verify with zero edges.
    s = make_scheduler("serial", simulate=True)
    _vec_episode(s)
    assert verify_scheduler(s) == []
    s.sync()
    s.shutdown()
    # Host reads bridge ordering across retired dependencies.
    s = make_scheduler("parallel")
    y = s.array(shape=(8,), dtype=np.float32, name="hy")
    xs = s.array(np.ones(8, np.float32), name="hx")
    s.launch(lambda a, b: (a * 2.0,), [const(xs), out(y)], name="W1",
             cost_s=1e-4)
    float(y[0])                       # host read: frontier barrier
    s.launch(lambda a, b: (a * 3.0,), [const(xs), out(y)], name="W2",
             cost_s=1e-4)
    assert verify_scheduler(s) == []
    s.verify()                        # raising form, same result
    s.sync()
    s.shutdown()


# ----------------------------------------------------------------------
# Sanitizer runtime mode
# ----------------------------------------------------------------------

def _mk_element(args, name, cost=1e-3):
    return ComputationalElement(fn=None, args=tuple(args), name=name,
                                cost_s=cost)


def test_sanitizer_unit_detects_all_three_race_shapes():
    s = make_scheduler("parallel", simulate=True)
    a = s.array(np.ones(16, np.float32), name="a")

    san = Sanitizer()
    w1, w2 = _mk_element([out(a)], "W1"), _mk_element([out(a)], "W2")
    san.pre_exec(w1)
    with pytest.raises(SanitizerError, match="WAW"):
        san.pre_exec(w2)                          # write-write overlap

    san = Sanitizer()
    r, w = _mk_element([const(a)], "R"), _mk_element([out(a)], "W")
    san.pre_exec(r)
    with pytest.raises(SanitizerError, match="WAR"):
        san.pre_exec(w)                           # write begins mid-read

    san = Sanitizer()
    w, r = _mk_element([out(a)], "W"), _mk_element([const(a)], "R")
    san.pre_exec(w)
    with pytest.raises(SanitizerError, match="RAW"):
        san.pre_exec(r)                           # read begins mid-write
    assert san.races_detected == 1
    s.shutdown()


def test_sanitizer_detects_torn_read():
    s = make_scheduler("parallel", simulate=True)
    a = s.array(np.ones(16, np.float32), name="a")
    san = Sanitizer()
    r = _mk_element([const(a)], "R")
    san.pre_exec(r)
    # A write the hooks never saw (lost instrumentation / out-of-band
    # mutation) bumps the version between the read's start and end.
    key = r.args[0].key
    san._state[key].version += 1
    with pytest.raises(SanitizerError, match="torn read"):
        san.post_exec(r)
    s.shutdown()


def test_sanitizer_checksum_catches_write_through_const():
    s = make_scheduler("parallel")
    a = s.array(np.ones(16, np.float32), name="a")   # host-only value
    san = Sanitizer(checksums=True)
    e = _mk_element([const(a)], "R")
    san.pre_exec(e)
    a.host[0] += 1.0                  # in-place mutation the DAG cannot see
    with pytest.raises(SanitizerError, match="write through const"):
        san.post_exec(e)
    s.shutdown()


def test_sim_executor_overlap_raises_through_hooks():
    s = make_scheduler("parallel", simulate=True, sanitize=True)
    a = s.array(np.ones(16, np.float32), name="a")
    e1, e2 = _mk_element([out(a)], "W1"), _mk_element([out(a)], "W2")
    # Bypass dependency inference: two conflicting writers, no parents, on
    # two lanes — they start at the same sim timestamp and must trip the
    # sanitizer the moment the second one begins.
    s.executor.submit([(e1, 0, ())])
    with pytest.raises(SanitizerError, match="WAW"):
        s.executor.submit([(e2, 1, ())])
    assert s.stats()["sanitizer_races_detected"] == 1


def test_sanitize_off_installs_no_hooks_and_is_bit_identical():
    def run(sanitize):
        s = make_scheduler("parallel", sanitize=sanitize)
        if not sanitize:
            assert s.executor.pre_exec is None
            assert s.executor.post_exec is None
            assert s.sanitizer is None
        x = s.array(np.linspace(0.25, 4.0, 512).astype(np.float32))
        y = s.array(shape=(512,), dtype=np.float32)
        z = s.array(shape=(512,), dtype=np.float32)
        sq = gr.function(lambda a, b: (a * a,), modes=("const", "out"),
                         name="good_sq", scheduler=s)
        add = gr.function(lambda a, b, c: (a + b,),
                          modes=("const", "const", "out"), name="good_add",
                          scheduler=s)
        sq(x, y)
        add(x, y, z)
        result = np.array(z)
        if sanitize:
            st = s.stats()
            assert st["sanitizer_elements_checked"] > 0
            assert st["sanitizer_races_detected"] == 0
        s.sync()
        s.shutdown()
        return result

    plain, sane = run(False), run(True)
    assert plain.tobytes() == sane.tobytes()      # bit-identical


def test_sanitized_scheduler_runs_clean_scenarios_green():
    from repro.benchsuite import build_task_parallel
    s = make_scheduler("parallel", simulate=True, sanitize=True)
    build_task_parallel(s, branches=3, chain=3, n=1 << 10)
    s.sync()
    st = s.stats()
    assert st["sanitizer_elements_checked"] > 0
    assert st["sanitizer_races_detected"] == 0
    # Captured plans are verified at capture time under sanitize=True.
    with s.capture("tp2"):
        build_task_parallel(s, branches=2, chain=2, n=1 << 10)
    s.sync()
    s.verify()
    s.shutdown()


# ----------------------------------------------------------------------
# Memory drift: structured report + monitor surfacing
# ----------------------------------------------------------------------

def test_memory_verify_raises_structured_drift_report():
    s = make_scheduler("parallel", simulate=True)
    _vec_episode(s)
    s.sync()
    assert s.memory.verify().ok
    pool = s.memory.pools[0]
    pool.resident_bytes += 4096           # seed ledger drift
    try:
        with pytest.raises(MemoryDriftError) as exc:
            s.memory.verify()
        report = exc.value.report
        assert not report.ok
        assert any("ledger" in p for p in report.problems)
        assert report.logical                 # structured diff present
        assert json.dumps(report.to_json())   # serializable
        # Non-raising form for samplers:
        assert not s.memory.verify(raise_on_drift=False).ok
    finally:
        pool.resident_bytes -= 4096
    assert s.memory.verify().ok
    s.shutdown()


def test_monitor_surfaces_drift_report():
    from repro.daemon import RuntimeMonitor
    s = make_scheduler("parallel", simulate=True)
    _vec_episode(s)
    s.sync()
    mon = RuntimeMonitor(s, interval_s=None, drift_grace=1)
    mon.sample_once()
    assert mon.stats()["monitor_drift_report"]["ok"]
    pool = s.memory.pools[0]
    pool.resident_bytes += 4096
    try:
        mon.sample_once()
        st = mon.stats()
        assert st["monitor_drift_alarms"] >= 1
        assert not st["monitor_drift_report"]["ok"]
        assert any("ledger" in p for p in st["monitor_drift_problems"])
    finally:
        pool.resident_bytes -= 4096
    s.shutdown()


# ----------------------------------------------------------------------
# Journal auditor
# ----------------------------------------------------------------------

def _record(jid, edges, state, t0=100.0):
    """One journal line: edges is a list of (src, dst) walked in order."""
    trans = [[src, dst, t0 + i] for i, (src, dst) in enumerate(edges)]
    return {"t": t0, "job": {"job_id": jid, "kind": "sleep", "params": {},
                             "tenant": "default", "priority": 0,
                             "deadline_s": None, "submit_t": t0,
                             "state": state, "reason": "", "result": None,
                             "attempts": 1, "transitions": trans}}


def _write_journal(lines):
    fd, path = tempfile.mkstemp(suffix=".jsonl")
    with os.fdopen(fd, "w") as fh:
        for rec in lines:
            fh.write((rec if isinstance(rec, str) else json.dumps(rec))
                     + "\n")
    return path


_GOOD_EDGES = [("queued", "admitted"), ("admitted", "running"),
               ("running", "finished")]


def test_journal_auditor_passes_clean_and_torn_tail():
    path = _write_journal([
        _record("j1", _GOOD_EDGES[:1], "admitted"),
        _record("j1", _GOOD_EDGES[:2], "running"),
        _record("j1", _GOOD_EDGES, "finished"),
        '{"t": 1, "job": {"job_id": "j2", "trunca',      # crash frontier
    ])
    audit = audit_journal(path)
    assert audit.ok and audit.torn_tail and audit.jobs == 1
    assert audit.records == 3 and audit.notes


@pytest.mark.parametrize("mutation,needle", [
    ("illegal_edge", "illegal"),
    ("rewrite", "rewritten"),
    ("state_mismatch", "last transition"),
    ("nonmonotone", "precedes"),
    ("torn_middle", "torn record"),
    ("empty_history", "empty transition"),
])
def test_journal_auditor_flags_every_mutation(mutation, needle):
    if mutation == "illegal_edge":
        lines = [_record("j1", [("queued", "running"),
                                ("running", "finished")], "finished")]
    elif mutation == "rewrite":
        lines = [_record("j1", _GOOD_EDGES[:2], "running"),
                 _record("j1", [("queued", "cancelled")], "cancelled")]
    elif mutation == "state_mismatch":
        lines = [_record("j1", _GOOD_EDGES, "running")]
    elif mutation == "nonmonotone":
        rec = _record("j1", _GOOD_EDGES, "finished")
        rec["job"]["transitions"][2][2] = 1.0        # time goes backwards
        lines = [rec]
    elif mutation == "torn_middle":
        lines = [_record("j1", _GOOD_EDGES[:1], "admitted"),
                 '{"t": 1, "job": {"job_id": "j1", "trunc',
                 _record("j1", _GOOD_EDGES[:2], "running")]
    else:
        lines = [_record("j1", [], "running")]
    audit = audit_journal(_write_journal(lines))
    assert not audit.ok
    assert any(needle in p for p in audit.problems), audit.problems


def test_jobstore_audit_and_daemon_cli_exit_codes(capsys):
    from repro.daemon.cli import main as daemon_main
    from repro.daemon.lifecycle import JobRecord, JobState
    from repro.daemon.store import JobStore

    with pytest.raises(ValueError, match="no journal"):
        JobStore(None).audit()

    tmp = tempfile.mkdtemp(prefix="analysis_store_")
    path = os.path.join(tmp, "jobs.jsonl")
    store = JobStore(path)
    job = JobRecord(job_id="j1", kind="sleep", submit_t=1.0)
    store.put(job)
    job.transition(JobState.ADMITTED, t=2.0)
    store.put(job)
    job.transition(JobState.RUNNING, t=3.0)
    job.transition(JobState.FINISHED, t=4.0)
    store.put(job)
    audit = store.audit()
    assert audit.ok and audit.jobs == 1 and audit.records == 3
    store.close(compact=False)

    assert daemon_main(["jobs", "--audit", "--store", path]) == 0
    capsys.readouterr()
    # Corrupt a middle record: the CLI must exit non-zero.
    lines = open(path).read().splitlines()
    lines.insert(1, '{"t": 1, "job": {"job_id": "j1", "trunc')
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    assert daemon_main(["jobs", "--audit", "--store", path]) == 1
    out = capsys.readouterr().out
    assert "torn record" in out


# ----------------------------------------------------------------------
# PlanVerificationError formatting
# ----------------------------------------------------------------------

def test_plan_verification_error_carries_violations():
    plan = _captured_plan()
    mut = _mutate_element(plan, 1, index=5)
    vs = verify_plan(mut)
    err = PlanVerificationError("vec", vs)
    assert err.violations == vs and "structure" in str(err)
