"""Chaos-injection + checkpoint-aware recovery + node blacklisting tests.

Everything here runs against a *seeded* FaultPlan (CHAOS_SEED, overridable in
CI) so injected-fault runs are bit-for-bit reproducible: same plan, same
failures, same recovery trajectory.
"""
import os
import time

import pytest

from repro.core import (
    AllocationError,
    ApplicationMaster,
    ChaosOOM,
    ContainerRequest,
    EventLog,
    FailureClass,
    FaultInjector,
    FaultKind,
    FaultPlan,
    FaultSpec,
    JobHistoryServer,
    MetricsAnalyzer,
    Node,
    NodeHealthTracker,
    Resource,
    ResourceManager,
    RetryPolicy,
    TaskDiagnostics,
    TonYClient,
    YarnLikeBackend,
    classify_exception,
    job_spec_from_props,
    make_cluster,
)
from repro.core.failures import diagnose_exception, is_oom_signature

pytestmark = pytest.mark.chaos

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "1234"))


def _job(workers=2, attempts=3):
    return job_spec_from_props({
        "tony.application.name": "chaos",
        "tony.application.max-attempts": str(attempts),
        "tony.worker.instances": str(workers),
        "tony.worker.memory": "1024",
        "tony.worker.gpus": "1",
        "tony.worker.node-label": "gpu",
    })


def make_step_program(steps: int, ckpt_every: int = 2, work_s: float = 0.0,
                      trace: list | None = None):
    """Minimal stand-in for the JAX train loop: steps through the chaos
    hook, honors the AM's resume_step, and publishes completed checkpoints
    — the full resume contract without JIT compile time."""

    def program(env, ctx):
        task_id = f"{env['TASK_TYPE']}:{env['TASK_INDEX']}"
        attempt = int(ctx.shared.get("attempt", 1))
        if not ctx.rendezvous(timeout=10):
            return 3
        if task_id != "worker:0":
            while not ctx.cancel.is_set() and not ctx.shared.get("done"):
                time.sleep(0.002)
            return 0
        start = int(ctx.shared.get("resume_step", 0))
        try:
            for step in range(start, steps):
                if ctx.cancel.is_set():
                    return 143
                ctx.chaos.check_step(task_id, attempt, step)
                if trace is not None:
                    trace.append((attempt, step))
                if work_s:
                    time.sleep(work_s)
                if (step + 1) % ckpt_every == 0:
                    ctx.shared["ckpt_step"] = step + 1
        finally:
            ctx.shared["done"] = True
        return 0

    return program


def _chaos_cluster(plan, *, health=None, **cluster_kw):
    ev = EventLog()
    rm = make_cluster(event_log=ev, chaos=FaultInjector(plan, events=ev),
                      health=health, **cluster_kw)
    return rm, ev


# ----------------------------------------------------------------------
# Plan + classification units


def test_fault_plan_seeded_generation_is_deterministic():
    p1 = FaultPlan.random_plan(CHAOS_SEED, steps=50, n_faults=4)
    p2 = FaultPlan.random_plan(CHAOS_SEED, steps=50, n_faults=4)
    assert p1 == p2 and len(p1.faults) == 4
    assert FaultPlan.random_plan(CHAOS_SEED + 1, steps=50, n_faults=4) != p1


def test_fault_spec_task_patterns():
    s = FaultSpec(FaultKind.KILL_TASK, task="worker:*")
    assert s.matches_task("worker:0") and s.matches_task("worker:7")
    assert not s.matches_task("ps:0")
    assert FaultSpec(FaultKind.KILL_TASK, task="*").matches_task("ps:3")
    assert FaultSpec(FaultKind.KILL_TASK, attempt=2).matches_attempt(2)
    assert not FaultSpec(FaultKind.KILL_TASK, attempt=2).matches_attempt(1)


def test_oom_signatures_classified_infra_with_flag():
    d = diagnose_exception("worker:0", MemoryError("alloc failed"))
    assert d.classification is FailureClass.INFRA and d.oom
    try:
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                           "to allocate 17179869184 bytes")
    except RuntimeError as e:
        d2 = diagnose_exception("worker:1", e)
    assert d2.classification is FailureClass.INFRA and d2.oom
    assert "(OOM)" in d2.describe() and d2.to_dict()["oom"] is True
    assert classify_exception(
        "RuntimeError", "CUDA_ERROR_OUT_OF_MEMORY: out of memory"
    ) is FailureClass.INFRA
    assert is_oom_signature("ChaosOOM", "")
    # plain crashes stay TRANSIENT, ImportError stays FATAL_USER
    d3 = diagnose_exception("w", RuntimeError("plain crash"))
    assert d3.classification is FailureClass.TRANSIENT and not d3.oom
    assert classify_exception(ImportError("x")) is FailureClass.FATAL_USER


def test_injector_oom_raises_xla_style_message():
    inj = FaultInjector(FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.OOM, task="worker:0", at_step=3)))
    inj.check_step("worker:0", 1, 2)  # no-op: wrong step
    with pytest.raises(ChaosOOM, match="RESOURCE_EXHAUSTED"):
        inj.check_step("worker:0", 1, 3)
    inj.check_step("worker:0", 1, 3)  # count budget spent: fires once


# ----------------------------------------------------------------------
# NodeHealthTracker units (fake clock)


def _infra_diag(oom=False):
    return TaskDiagnostics("worker:0", 1, FailureClass.INFRA,
                           exception_type="ChaosOOM" if oom else "",
                           message="boom", oom=oom)


def test_node_health_blacklist_and_parole():
    t = [0.0]
    ev = EventLog()
    tr = NodeHealthTracker(threshold=2, parole_s=10.0, clock=lambda: t[0],
                           events=ev)
    assert not tr.record_failure("n0", _infra_diag())
    assert tr.record_failure("n0", _infra_diag(oom=True))  # tipped over
    assert tr.is_blacklisted("n0") and tr.blacklisted() == ["n0"]
    assert ev.count("node_blacklisted") == 1
    assert ev.of_kind("node_blacklisted")[0].payload["oom"] is True
    t[0] = 9.9
    assert tr.is_blacklisted("n0")
    t[0] = 10.0  # parole: allowed back, one strike from re-blacklist
    assert not tr.is_blacklisted("n0")
    assert ev.count("node_paroled") == 1
    assert tr.record_failure("n0", _infra_diag())  # single strike re-trips
    assert tr.is_blacklisted("n0")


def test_node_health_parole_edge_restrike_vs_clean_wipe():
    """The parole contract's two exits: a paroled node is ONE strike from
    re-blacklisting (not a clean slate), but a clean attempt wipes every
    strike — including the parole residue."""
    t = [0.0]
    ev = EventLog()
    tr = NodeHealthTracker(threshold=2, parole_s=10.0, clock=lambda: t[0],
                           events=ev)
    tr.record_failure("n0", _infra_diag())
    assert tr.record_failure("n0", _infra_diag())      # blacklisted
    t[0] = 10.0
    assert not tr.is_blacklisted("n0")                 # paroled
    assert tr.snapshot()["failures"]["n0"] == tr.threshold - 1
    # exit A: one more INFRA strike re-blacklists immediately
    assert tr.record_failure("n0", _infra_diag())
    assert tr.is_blacklisted("n0")
    assert ev.count("node_blacklisted") == 2 and ev.count("node_paroled") == 1
    # exit B (fresh tracker): a clean attempt after parole wipes strikes, so
    # one later strike must NOT re-blacklist (it is strike 1 of 2 again)
    t2 = [0.0]
    tr2 = NodeHealthTracker(threshold=2, parole_s=10.0, clock=lambda: t2[0])
    tr2.record_failure("n1", _infra_diag())
    tr2.record_failure("n1", _infra_diag())
    t2[0] = 10.0
    assert not tr2.is_blacklisted("n1")
    tr2.record_success("n1")                           # clean attempt
    assert tr2.snapshot()["failures"] == {}
    assert not tr2.record_failure("n1", _infra_diag())
    assert not tr2.is_blacklisted("n1")
    assert tr2.record_failure("n1", _infra_diag())     # second strike trips


def test_node_health_only_infra_counts_and_success_resets():
    tr = NodeHealthTracker(threshold=1)
    transient = TaskDiagnostics("w", 1, FailureClass.TRANSIENT, message="x")
    fatal = TaskDiagnostics("w", 1, FailureClass.FATAL_USER, message="x")
    assert not tr.record_failure("n0", transient)
    assert not tr.record_failure("n0", fatal)
    assert not tr.is_blacklisted("n0")
    tr2 = NodeHealthTracker(threshold=2)
    tr2.record_failure("n1", _infra_diag())
    tr2.record_success("n1")                      # clean attempt wipes strikes
    assert not tr2.record_failure("n1", _infra_diag())
    assert not tr2.is_blacklisted("n1")


# ----------------------------------------------------------------------
# Tentpole: seeded kill -> next attempt resumes from the checkpoint


def test_chaos_kill_resumes_next_attempt_from_checkpoint():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.KILL_TASK, task="worker:0", attempt=1, at_step=5))
    rm, ev = _chaos_cluster(plan)
    trace = []
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        _job(), make_step_program(8, ckpt_every=2, trace=trace), timeout=60)
    assert res.succeeded and len(res.attempts) == 2
    # attempt 1 died at step 5 with a classified, chaos-attributed failure
    d = res.diagnostics["a1/worker:0"]
    assert d.exception_type == "ChaosKill"
    assert d.classification is FailureClass.TRANSIENT
    assert ev.count("chaos_injected") == 1
    assert ev.of_kind("chaos_injected")[0].payload["fault"] == "kill_task"
    assert ev.of_kind("chaos_injected")[0].payload["seed"] == CHAOS_SEED
    # attempt 2 resumed from the step-4 checkpoint, not step 0
    assert res.attempts[0].checkpoint_step == 4
    assert res.attempts[1].resume_step == 4
    assert res.resumed_attempts == {2: 4}
    resumed = ev.of_kind("attempt_resumed")
    assert len(resumed) == 1 and resumed[0].payload["resume_step"] == 4
    a2_steps = [s for a, s in trace if a == 2]
    assert a2_steps and a2_steps[0] == 4 and min(a2_steps) > 0
    assert not rm.live_containers() and rm.invariants_ok()


def test_chaos_kill_resumes_real_training_from_checkpoint(tmp_path):
    """The full JAX path: chaos kills the chief at step 6; attempt 2
    restores model+optimizer state via Checkpointer.restore from step 4 and
    trains on (training step counter > 0 on attempt 2)."""
    from repro.configs import get_config
    from repro.launch.programs import make_train_program

    cfg = get_config("tony-paper-mlp").replace(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, max_position=64)
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.KILL_TASK, task="worker:0", attempt=1, at_step=6))
    rm, ev = _chaos_cluster(plan)
    seen = []
    prog = make_train_program(
        cfg, steps=10, batch_size=4, seq_len=16,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=4,
        on_step=lambda s, m: seen.append(s))
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(_job(), prog,
                                                       timeout=300)
    assert res.succeeded and len(res.attempts) == 2
    assert res.diagnostics["a1/worker:0"].exception_type == "ChaosKill"
    # AM-driven resume: attempt 2's first training step is 4, not 0
    assert res.resumed_attempts == {2: 4}
    a2_first = seen[seen.index(5) + 1]   # first step after attempt 1's last
    assert a2_first == 4 and a2_first > 0
    assert max(seen) == 9
    assert ev.count("attempt_resumed") == 1


# ----------------------------------------------------------------------
# Tentpole: K INFRA failures on one node -> blacklisted, reallocation avoids


def test_node_blacklisted_after_k_oom_failures_allocations_avoid_it():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.OOM, task="worker:0", attempt=0, at_step=2,
                  count=2))
    ev = EventLog()
    health = NodeHealthTracker(threshold=2, parole_s=600.0, events=ev)
    rm = make_cluster(num_gpu_nodes=3, num_cpu_nodes=1, event_log=ev,
                      chaos=FaultInjector(plan, events=ev), health=health)
    job = _job(attempts=3)
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        job, make_step_program(4, ckpt_every=2), timeout=60)
    assert res.succeeded and len(res.attempts) == 3
    # both OOMs were INFRA-classified with the oom flag
    for key in ("a1/worker:0", "a2/worker:0"):
        assert res.diagnostics[key].classification is FailureClass.INFRA
        assert res.diagnostics[key].oom
    # the node that hosted worker:0 ate both OOMs and got blacklisted
    bad = res.attempts[0].nodes["worker:0"]
    assert res.attempts[1].nodes["worker:0"] == bad
    bl = ev.of_kind("node_blacklisted")
    assert len(bl) == 1 and bl[0].payload["node"] == bad
    assert bl[0].payload["infra_failures"] == 2 and bl[0].payload["oom"]
    # attempt 3's allocations exclude the blacklisted node
    assert bad not in res.attempts[2].nodes.values()
    assert res.blacklisted_nodes == [bad]
    # recovery was checkpoint-aware throughout (resume from step 2)
    assert res.resumed_attempts == {2: 2, 3: 2}
    # history summary surfaces blacklist + resumes; timeline carries the
    # recovery events
    hist = JobHistoryServer()
    hist.record(job, res)
    s = hist.summary(res.app_id)
    assert s["blacklisted_nodes"] == [bad]
    assert s["resumed_attempts"] == {2: 2, 3: 2}
    assert s["diagnostics"]["a1/worker:0"]["oom"] is True
    timeline_kinds = {e.kind for e in ev.failure_timeline()}
    assert {"node_blacklisted", "attempt_resumed",
            "chaos_injected"} <= timeline_kinds
    assert any(g.kind == "oom" for g in MetricsAnalyzer().analyze(job, res))
    assert not rm.live_containers() and rm.invariants_ok()


# ----------------------------------------------------------------------
# Chaos heartbeat drop + preemption


def test_chaos_heartbeat_drop_becomes_classified_timeout():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.DROP_HEARTBEATS, task="worker:0", attempt=1,
                  duration_s=30.0))
    ev = EventLog()
    rm = make_cluster(event_log=ev, chaos=FaultInjector(plan, events=ev))
    job = _job(attempts=1)
    app_id = rm.submit_application(job.name, job.queue)

    def long_running(env, ctx):
        ctx.rendezvous(timeout=10)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not ctx.cancel.is_set():
            time.sleep(0.01)
        return 0

    am = ApplicationMaster(rm, app_id, job, long_running,
                           retry_policy=RetryPolicy(max_attempts=1))
    am.heartbeat_timeout_s = 0.25
    res = am.run()
    assert not res.succeeded
    d = res.diagnostics["a1/worker:0"]
    assert d.exception_type == "HeartbeatTimeout"
    assert d.classification is FailureClass.TRANSIENT
    assert ev.count("heartbeat_lost") == 1
    assert ev.of_kind("chaos_injected")[0].payload["fault"] == "drop_heartbeats"
    assert not rm.live_containers() and rm.invariants_ok()


def test_chaos_preemption_counts_infra_and_job_recovers():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.PREEMPT, task="worker:0", attempt=1,
                  after_s=0.05))
    ev = EventLog()
    health = NodeHealthTracker(threshold=1, parole_s=600.0, events=ev)
    rm = make_cluster(event_log=ev, chaos=FaultInjector(plan, events=ev),
                      health=health)
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        _job(), make_step_program(60, ckpt_every=10, work_s=0.005),
        timeout=60)
    assert res.succeeded and len(res.attempts) == 2
    d = res.diagnostics["a1/worker:0"]
    assert d.exit_status == 137 and d.classification is FailureClass.INFRA
    assert ev.of_kind("chaos_injected")[0].payload["fault"] == "preempt"
    # the preemption counted as an INFRA strike against the hosting node
    # (threshold=1 -> immediate blacklist) and attempt 2 avoided it
    bad = res.attempts[0].nodes["worker:0"]
    bl = ev.of_kind("node_blacklisted")
    assert len(bl) == 1 and bl[0].payload["node"] == bad
    assert bad not in res.attempts[1].nodes.values()
    assert res.blacklisted_nodes == [bad]
    assert not rm.live_containers() and rm.invariants_ok()


# ----------------------------------------------------------------------
# Satellite: RM under chaos allocation failures + unfittable gangs


def test_chaos_allocation_failure_mid_gang_leaks_nothing():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.FAIL_ALLOCATION, after_allocs=1, count=1))
    ev = EventLog()
    rm = ResourceManager([Node(f"n{i}", Resource(8192, 8, 4)) for i in range(2)],
                         event_log=ev, chaos=FaultInjector(plan, events=ev))
    app = rm.submit_application("gang", "default")
    req = ContainerRequest(Resource(1024, 1, 1))
    # first allocate succeeds, second is chaos-failed -> the whole gang
    # rolls back and nothing leaks
    with pytest.raises(AllocationError, match="chaos"):
        rm.allocate_many(app, req, 2)
    assert not rm.live_containers()
    assert rm.invariants_ok()
    assert ev.count("allocation_chaos_failed") == 1
    # chaos budget spent: the retry succeeds
    got = rm.allocate_many(app, req, 2)
    assert len(got) == 2 and rm.invariants_ok()
    for c in got:
        rm.release(c.container_id)
    assert not rm.live_containers() and rm.invariants_ok()


def test_am_negotiation_rides_through_chaos_allocation_failure():
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.FAIL_ALLOCATION, count=1))
    rm, ev = _chaos_cluster(plan)
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        _job(), make_step_program(2, ckpt_every=1), timeout=60)
    # a single injected allocation failure is absorbed inside the
    # negotiation window without burning an app attempt
    assert res.succeeded and len(res.attempts) == 1
    assert ev.count("allocation_chaos_failed") == 1
    assert ev.count("negotiation_waiting") == 1
    assert not rm.live_containers() and rm.invariants_ok()


def test_gang_that_cannot_fit_fails_cleanly_without_leaks():
    ev = EventLog()
    rm = make_cluster(num_gpu_nodes=1, num_cpu_nodes=0, gpus_per_node=2,
                      event_log=ev)
    job = _job(workers=4, attempts=1)       # 4 GPU workers, cluster has 2
    app_id = rm.submit_application(job.name, job.queue)
    am = ApplicationMaster(rm, app_id, job, make_step_program(2),
                           retry_policy=RetryPolicy(max_attempts=1))
    am.NEGOTIATION_TIMEOUT_S = 0.3
    res = am.run()
    assert not res.succeeded
    assert res.attempts[0].failed_tasks == ["__allocation__"]
    # try_preempt_for found no over-share victims: nothing was preempted
    assert ev.count("container_preempted") == 0
    assert not rm.live_containers()
    assert rm.invariants_ok()


class _ModuleProxy:
    """Stand-in for a module the checkpointer imported, with chosen
    attributes overridden — patches stay local to the checkpointer module
    instead of mutating numpy/json/os globally."""

    def __init__(self, mod, **overrides):
        self._mod = mod
        self._overrides = overrides

    def __getattr__(self, name):
        if name in self._overrides:
            return self._overrides[name]
        return getattr(self._mod, name)


def test_checkpoint_kill_points_never_expose_uncommitted_step(tmp_path,
                                                              monkeypatch):
    """Deterministic twin of the hypothesis property (test_property.py):
    hard-kill the checkpoint writer at each op inside save_pytree — inside
    a leaf file's write, between two leaf files, during the COMMIT-marker
    write, and at the atomic rename — leaving its debris behind (a real
    SIGKILL runs no finally); latest_step/restore must never observe the
    uncommitted step."""
    import json
    import shutil

    import numpy as np

    import repro.checkpoint.checkpointer as ck
    from repro.core import ChaosKill

    tree1 = {"b": np.arange(3, dtype=np.float32),
             "w": np.ones((2, 2), np.float32)}
    tree2 = {"b": np.full((3,), 5.0, np.float32),
             "w": np.full((2, 2), 7.0, np.float32)}
    real_write_leaf = ck._write_leaf

    def killer(*a, **k):
        raise ChaosKill("chaos: checkpoint writer killed mid-op")

    def torn_leaf(path, arr):              # half the bytes land, then death
        with open(path, "wb") as f:
            f.write(ck._byte_view(np.ascontiguousarray(arr))[:arr.nbytes // 2])
        killer()

    def second_leaf(path, arr):            # the first leaf lands whole
        if os.path.basename(path) != "leaf_0.bin":
            killer()
        real_write_leaf(path, arr)

    kill_points = {
        "during_array_write": (ck, "_write_leaf", torn_leaf),
        "between_leaf_files": (ck, "_write_leaf", second_leaf),
        "during_commit_write": (ck, "json", _ModuleProxy(json, dump=killer)),
        "at_atomic_rename": (ck, "os", _ModuleProxy(os, replace=killer)),
    }
    template = {"b": np.zeros((3,), np.float32),
                "w": np.zeros((2, 2), np.float32)}
    for label, (mod, attr, patched) in kill_points.items():
        d = str(tmp_path / label)
        ck.save_pytree(tree1, d, 1)            # committed baseline
        monkeypatch.setattr(mod, attr, patched)
        # a hard kill runs no cleanup: keep the staging debris on disk
        monkeypatch.setattr(ck, "shutil",
                            _ModuleProxy(shutil, rmtree=lambda *a, **k: None))
        with pytest.raises(ChaosKill):
            ck.save_pytree(tree2, d, 2)
        monkeypatch.undo()
        # debris may exist, but the committed view is untouched
        assert ck.latest_step(d) == 1, label
        assert not ck.is_committed(d, 2), label
        back = ck.restore_pytree(template, d)
        for key in tree1:
            np.testing.assert_array_equal(back[key], tree1[key])
    # a marker-less step dir (manual copy, interrupted writer) is equally
    # invisible to latest_step and restore
    d = str(tmp_path / "during_array_write")
    os.makedirs(os.path.join(d, "step_00000009"), exist_ok=True)
    assert ck.latest_step(d) == 1
    with pytest.raises(FileNotFoundError):
        ck.restore_pytree(tree1, d, 9)


def test_try_preempt_for_under_chaos_allocation_failures():
    # after_allocs=2: let the hog's two allocations through, chaos-fail the
    # prod queue's first ask
    plan = FaultPlan(seed=CHAOS_SEED).add(
        FaultSpec(FaultKind.FAIL_ALLOCATION, after_allocs=2, count=1))
    ev = EventLog()
    rm = ResourceManager([Node("n0", Resource(10_000, 100, 0))],
                         queues={"prod": 0.8, "adhoc": 0.2}, elastic=True,
                         event_log=ev, chaos=FaultInjector(plan, events=ev))
    a_hog = rm.submit_application("hog", "adhoc")
    hogs = [rm.allocate(a_hog, ContainerRequest(Resource(4000, 10, 0)))
            for _ in range(2)]
    assert rm.queue_over_share("adhoc")
    a_prod = rm.submit_application("p", "prod")
    ask = ContainerRequest(Resource(6000, 10, 0))
    with pytest.raises(AllocationError, match="chaos"):   # injected failure
        rm.allocate(a_prod, ask)
    assert rm.invariants_ok() and len(rm.live_containers()) == 2
    n = rm.try_preempt_for(a_prod, ask)
    assert n >= 1 and rm.invariants_ok()
    c = rm.allocate(a_prod, ask)                          # chaos budget spent
    assert c is not None and rm.invariants_ok()
    # conservation held across chaos + preemption: no leaked containers
    live = rm.live_containers()
    assert len(live) == len(hogs) - n + 1
