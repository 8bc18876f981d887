"""AsyncCheckpointer contract: depth-1 backpressure, publish-after-commit
ordering, sticky deferred writer errors, and the re-checkpoint swap windows
of ``save_pytree`` (a kill at any point leaves a committed copy visible).
"""
import os
import threading
import time

import numpy as np
import pytest

import repro.checkpoint.checkpointer as ck
from repro.checkpoint import (
    AsyncCheckpointer,
    Checkpointer,
    latest_step,
    restore_pytree,
    save_pytree,
    tree_nbytes,
)


def _tree(v: float):
    return {"w": np.full((8,), v, np.float32),
            "b": np.full((3,), v * 10, np.float32)}


def _wait_for(pred, timeout=5.0):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition never became true")
        time.sleep(0.005)


# ----------------------------------------------------------------------
# backpressure: the hand-off slot is depth 1

def test_second_save_blocks_until_writer_commits(tmp_path, monkeypatch):
    gate = threading.Event()
    in_writer = threading.Event()
    active = []
    real = ck._write

    def gated(flat, directory, step, pre_commit=None):
        active.append(step)
        assert len(active) == 1, "two writes in flight (depth > 1)"
        in_writer.set()
        gate.wait(5)
        try:
            return real(flat, directory, step, pre_commit=pre_commit)
        finally:
            active.remove(step)

    monkeypatch.setattr(ck, "_write", gated)
    c = AsyncCheckpointer(str(tmp_path))
    try:
        t0 = time.monotonic()
        c.save(_tree(1.0), 1)            # hand-off only: returns immediately
        assert time.monotonic() - t0 < 1.0
        assert in_writer.wait(5)

        done2 = threading.Event()
        t = threading.Thread(target=lambda: (c.save(_tree(2.0), 2),
                                             done2.set()), daemon=True)
        t.start()
        time.sleep(0.15)
        # write 1 still in flight -> save 2 must be blocked, not queued
        assert not done2.is_set(), "second save returned while one in flight"
        gate.set()
        assert done2.wait(5), "second save never unblocked after commit"
        c.flush()
    finally:
        c.close()
    assert latest_step(str(tmp_path)) == 2


def test_publish_only_after_commit(tmp_path):
    staged = threading.Event()
    gate = threading.Event()
    commits = []

    def hook(step):                      # pre_commit: arrays staged, no COMMIT
        staged.set()
        gate.wait(5)

    c = AsyncCheckpointer(str(tmp_path), chaos_hook=hook,
                          on_commit=lambda s, p, dur, nb: commits.append(s))
    try:
        c.save(_tree(1.0), 2)
        assert staged.wait(5)
        # writer is paused between staging and COMMIT: nothing may be
        # published or visible yet
        assert commits == []
        assert latest_step(str(tmp_path)) is None
        gate.set()
        c.flush()
        assert commits == [2]
        assert latest_step(str(tmp_path)) == 2
    finally:
        c.close()


def test_writer_error_is_sticky_and_reraises_on_caller(tmp_path):
    boom = RuntimeError("chaos: killed inside the write")

    def hook(step):
        if step == 4:
            raise boom

    c = AsyncCheckpointer(str(tmp_path), chaos_hook=hook)
    try:
        c.save(_tree(1.0), 2)
        c.flush()                        # step 2 commits fine
        assert latest_step(str(tmp_path)) == 2
        c.save(_tree(2.0), 4)            # dies in the writer window
        with pytest.raises(RuntimeError, match="killed inside"):
            c.flush()
        with pytest.raises(RuntimeError, match="killed inside"):
            c.save(_tree(3.0), 6)        # sticky: the task must die, not retry
        # the failed write never became visible
        assert latest_step(str(tmp_path)) == 2
    finally:
        c.close()


def test_close_drains_pending_write_and_rejects_new_saves(tmp_path):
    c = AsyncCheckpointer(str(tmp_path))
    c.save(_tree(1.0), 2)
    c.close()                            # graceful: pending write commits
    c.close()                            # idempotent
    assert latest_step(str(tmp_path)) == 2
    with pytest.raises(RuntimeError, match="closed"):
        c.save(_tree(2.0), 4)


def test_async_matches_sync_on_disk(tmp_path):
    tree = _tree(3.5)
    sync_dir, async_dir = str(tmp_path / "s"), str(tmp_path / "a")
    Checkpointer(sync_dir).save(tree, 7)
    c = AsyncCheckpointer(async_dir)
    c.save(tree, 7)
    c.flush()
    c.close()
    a = restore_pytree(_tree(0.0), async_dir, 7)
    s = restore_pytree(_tree(0.0), sync_dir, 7)
    for k in tree:
        np.testing.assert_array_equal(a[k], s[k])
    assert tree_nbytes(a) == tree_nbytes(tree)


# ----------------------------------------------------------------------
# re-checkpoint swap windows: overwriting step N must never lose step N

def test_kill_at_replace_keeps_old_committed_copy(tmp_path, monkeypatch):
    d = str(tmp_path)
    save_pytree(_tree(1.0), d, 5)
    monkeypatch.setattr(ck.shutil, "rmtree", lambda *a, **k: None)
    monkeypatch.setattr(ck.os, "replace",
                        lambda *a: (_ for _ in ()).throw(OSError("killed")))
    with pytest.raises(OSError):
        save_pytree(_tree(2.0), d, 5)    # dies after the old dir moved aside
    monkeypatch.undo()
    # the aside copy still counts as committed and restores the OLD content
    assert latest_step(d) == 5
    got = restore_pytree(_tree(0.0), d, 5)
    np.testing.assert_array_equal(got["w"], _tree(1.0)["w"])


def test_kill_during_aside_cleanup_shows_new_content(tmp_path, monkeypatch):
    d = str(tmp_path)
    save_pytree(_tree(1.0), d, 5)
    monkeypatch.setattr(ck.shutil, "rmtree", lambda *a, **k: None)
    save_pytree(_tree(2.0), d, 5)        # replace lands, cleanup "killed"
    monkeypatch.undo()
    assert latest_step(d) == 5
    got = restore_pytree(_tree(0.0), d, 5)
    np.testing.assert_array_equal(got["w"], _tree(2.0)["w"])
    # gc clears the now-redundant aside once the final dir is committed
    Checkpointer(d)._gc()
    assert not [e for e in os.listdir(d) if e.startswith(".aside-")]


# ----------------------------------------------------------------------
# a write still in flight when the chief dies or its attempt is torn down

@pytest.mark.parametrize("event", ["kill", "teardown"])
def test_write_in_flight_past_the_heartbeat_timeout(event, tmp_path,
                                                    monkeypatch):
    """The chief's background write of step ``every`` outlasts the AM's
    heartbeat timeout, and meanwhile either the planned kill lands
    ("kill": the training thread waits in ``close`` for the write) or the
    attempt is torn down ("teardown": the executor drains the writer before
    it reports). The task heartbeats all through both, so each is diagnosed
    as what it is: the kill as ChaosKill, resumed from the committed step;
    the teardown as exit 143, not as a lost heartbeat."""
    from repro.configs import get_config
    from repro.core import (EventLog, FaultInjector, FaultKind, FaultPlan,
                            FaultSpec, TonYClient, YarnLikeBackend,
                            job_spec_from_props, make_cluster)
    from repro.core import appmaster
    from repro.launch.programs import make_train_program

    every, write_s = 3, 5.0
    monkeypatch.setattr(appmaster, "HEARTBEAT_TIMEOUT_S", 2.0)
    writing = threading.Event()
    original = ck._write

    def slow_write(flat, directory, step, pre_commit=None):
        if step == every:
            writing.set()
            time.sleep(write_s)
        return original(flat, directory, step, pre_commit=pre_commit)

    monkeypatch.setattr(ck, "_write", slow_write)
    plan = FaultPlan(seed=0)
    if event == "kill":
        plan = plan.add(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  attempt=1, at_step=every))
    events = EventLog()
    rm = make_cluster(event_log=events,
                      chaos=FaultInjector(plan, events=events))
    job = job_spec_from_props({
        "tony.application.name": f"write-in-flight-{event}",
        "tony.application.max-attempts": "2" if event == "kill" else "1",
        "tony.worker.instances": "1", "tony.worker.memory": "1024"})
    cfg = get_config("tony-paper-mlp").replace(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, max_position=64)
    prog = make_train_program(
        cfg, steps=2 * every if event == "kill" else 10**6, batch_size=4,
        seq_len=16, ckpt_dir=str(tmp_path / "ck"), ckpt_every=every)
    chief = []

    def program(env, ctx):
        if env["TASK_INDEX"] == "0":
            chief.append(ctx)
        return prog(env, ctx)

    if event == "teardown":
        def tear_down():
            if writing.wait(120):
                chief[-1].cancel.set()

        threading.Thread(target=tear_down, daemon=True).start()
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(job, program,
                                                       timeout=300)
    assert writing.is_set()
    described = {k: d.describe() for k, d in res.diagnostics.items()}
    assert not any("HeartbeatTimeout" in d for d in described.values())
    assert latest_step(str(tmp_path / "ck")) >= every
    if event == "kill":
        assert res.succeeded and res.resumed_attempts == {2: every}
        assert list(described) == ["a1/worker:0"]
        assert "ChaosKill" in described["a1/worker:0"]
    else:
        assert not res.succeeded
        assert list(described) == ["a1/worker:0"]
        assert "exit status 143" in described["a1/worker:0"]


def test_hung_writer_fails_the_task_after_the_drain_deadline(monkeypatch):
    """A flusher that never returns: the task heartbeats through the drain
    for ``DRAIN_TIMEOUT_S`` and then reports the hang as its own failure,
    so the job ends instead of waiting on the writer for ever."""
    from repro.core import (EventLog, TonYClient, YarnLikeBackend,
                            job_spec_from_props, make_cluster)
    from repro.core import appmaster
    from repro.core.task_executor import TaskExecutor

    monkeypatch.setattr(appmaster, "HEARTBEAT_TIMEOUT_S", 0.5)
    monkeypatch.setattr(TaskExecutor, "DRAIN_TIMEOUT_S", 2.0)
    stuck = threading.Event()

    def program(env, ctx):
        ctx.register_flusher(stuck.wait)
        return 0

    job = job_spec_from_props({
        "tony.application.name": "hung-writer",
        "tony.application.max-attempts": "1",
        "tony.worker.instances": "1", "tony.worker.memory": "1024"})
    t0 = time.monotonic()
    try:
        res = TonYClient(YarnLikeBackend(make_cluster(
            event_log=EventLog()))).run_and_wait(job, program, timeout=60)
    finally:
        stuck.set()
    assert time.monotonic() - t0 < 30
    assert not res.succeeded
    [(task, diag)] = res.diagnostics.items()
    assert task == "a1/worker:0"
    assert "DrainTimeout" in diag.describe(), diag.describe()
