"""End-to-end system tests: real JAX training jobs through the full TonY path
(client -> RM -> AM -> executors -> train loop), including checkpoint-restore
fault tolerance — the paper's §2.2/§3 behaviour."""
import importlib.util
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import pytest

from repro.checkpoint import latest_step
from repro.configs import get_config
from repro.core import (
    EventLog,
    FaultInjector,
    FaultKind,
    FaultPlan,
    FaultSpec,
    TonYClient,
    YarnLikeBackend,
    job_spec_from_props,
    make_cluster,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.programs import make_train_program

ROOT = Path(__file__).resolve().parents[1]

CFG = get_config("tony-paper-mlp").replace(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    d_ff=128, vocab_size=128, max_position=64)


def _job(workers=2, ps=1):
    props = {
        "tony.application.name": "e2e",
        "tony.worker.instances": str(workers),
        "tony.worker.memory": "2048",
        "tony.worker.gpus": "1",
        "tony.worker.node-label": "gpu",
        "tony.ps.instances": str(ps),
        "tony.ps.memory": "1024",
        "tony.ps.node-label": "highmem",
    }
    return job_spec_from_props(props)


def _chaos_cluster(*faults):
    """A cluster whose chaos plan fires ``faults``; its event log is
    ``rm.events``."""
    plan = FaultPlan(seed=1234)
    for fault in faults:
        plan = plan.add(fault)
    events = EventLog()
    return make_cluster(event_log=events,
                        chaos=FaultInjector(plan, events=events))


def test_e2e_training_job_succeeds_and_loss_drops(tmp_path):
    rm = make_cluster()
    client = TonYClient(YarnLikeBackend(rm))
    losses = []
    prog = make_train_program(
        CFG, steps=25, batch_size=8, seq_len=32,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=10,
        on_step=lambda s, m: losses.append(m["loss"]))
    res = client.run_and_wait(_job(), prog, timeout=300)
    assert res.succeeded and len(res.attempts) == 1
    assert losses[0] > losses[-1]
    assert os.path.exists(tmp_path / "ck")
    # chief reported real metrics through the executor
    mkeys = [k for k in res.metrics if k.endswith("worker:0")]
    assert mkeys and res.metrics[mkeys[0]]["steps"] == 25.0


def test_e2e_fault_tolerance_restores_from_checkpoint(tmp_path):
    """Kill the chief mid-run on attempt 1; AM relaunches; training resumes
    from the last checkpoint, not from scratch (the paper's §2.2 contract)."""
    # crash attempt 1 at step 13 (after the step-10 ckpt)
    rm = _chaos_cluster(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  attempt=1, at_step=13))
    client = TonYClient(YarnLikeBackend(rm))
    seen_steps = []
    prog = make_train_program(
        CFG, steps=20, batch_size=8, seq_len=32,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
        on_step=lambda s, m: seen_steps.append(s))
    res = client.run_and_wait(_job(), prog, timeout=300)
    assert res.succeeded
    assert len(res.attempts) == 2
    assert "worker:0" in res.attempts[0].failed_tasks
    # the crash was attributed: real traceback + TRANSIENT classification,
    # and the retry that saved the job is visible in the event log
    diag = res.diagnostics["a1/worker:0"]
    assert diag.classification.value == "TRANSIENT"
    assert diag.exception_type == "ChaosKill"
    assert ("chaos: injected kill of worker:0 at attempt=1 step=13"
            in diag.traceback)
    assert rm.events.count("retry_scheduled") == 1
    # attempt 2 resumed at 10 (the checkpoint), not 0
    restart_points = [s for i, s in enumerate(seen_steps[1:], 1)
                      if s <= seen_steps[i - 1]]
    assert restart_points == [10]
    assert max(seen_steps) == 19
    # the relaunch negotiated fresh containers
    assert rm.events.count("container_allocated") == 6
    assert rm.invariants_ok()


def test_e2e_new_cluster_spec_each_attempt(tmp_path):
    rm = _chaos_cluster(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  attempt=1, at_step=2))
    client = TonYClient(YarnLikeBackend(rm))
    prog = make_train_program(
        CFG, steps=8, batch_size=4, seq_len=16,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=4)
    res = client.run_and_wait(_job(workers=1, ps=1), prog, timeout=300)
    assert res.succeeded
    s1 = res.attempts[0].cluster_spec
    s2 = res.attempts[1].cluster_spec
    assert s1 is not None and s2 is not None
    assert s1 != s2  # fresh ports/containers -> new global spec (paper §2.2)


def test_e2e_last_checkpoint_committed_when_job_returns(tmp_path):
    """The chief flushes its checkpoint writer before it succeeds: when the
    job returns, the final step has committed, and each save committed
    once."""
    rm = _chaos_cluster()
    ckpt_dir = str(tmp_path / "ck")
    prog = make_train_program(CFG, steps=8, batch_size=4, seq_len=16,
                              ckpt_dir=ckpt_dir, ckpt_every=4)
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        _job(workers=1, ps=1), prog, timeout=300)
    assert res.succeeded and len(res.attempts) == 1
    assert latest_step(ckpt_dir) == 8
    assert [e.payload["step"] for e in rm.events.of_kind("ckpt_committed")
            ] == [4, 8]


@pytest.mark.chaos
def test_e2e_kill_inside_checkpoint_write_resumes_from_committed_step(
        tmp_path):
    """A kill inside the background write of step 8: the relaunch resumes
    from step 4, the last committed step, never from 8."""
    rm = _chaos_cluster(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  at_step=8, in_ckpt_write=True))
    seen_steps = []
    prog = make_train_program(
        CFG, steps=12, batch_size=4, seq_len=16,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=4,
        on_step=lambda s, m: seen_steps.append(s))
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(
        _job(workers=1, ps=1), prog, timeout=300)
    assert res.succeeded and len(res.attempts) == 2
    assert res.resumed_attempts == {2: 4}
    assert rm.events.count("chaos_injected") == 1
    restart_points = [s for i, s in enumerate(seen_steps[1:], 1)
                      if s <= seen_steps[i - 1]]
    assert restart_points == [4]
    assert latest_step(str(tmp_path / "ck")) == 12


def test_e2e_chief_leaves_no_thread_running(tmp_path):
    """The chief closes its checkpoint writer and its prefetching loader,
    and both ``close`` calls join their thread."""
    before = set(threading.enumerate())
    ckpt_dir = str(tmp_path / "ck")
    prog = make_train_program(CFG, steps=8, batch_size=4, seq_len=16,
                              ckpt_dir=ckpt_dir, ckpt_every=4)
    res = TonYClient(YarnLikeBackend(make_cluster())).run_and_wait(
        _job(workers=1, ps=1), prog, timeout=300)
    assert res.succeeded
    left = [t.name for t in threading.enumerate() if t not in before]
    assert f"ckpt-writer:{ckpt_dir}" not in left, left
    assert "prefetch-loader" not in left, left


@pytest.mark.parametrize("faults,rc,status", [(0, 0, "SUCCEEDED"),
                                              (5, 1, "FAILED")])
def test_train_cli_exit_code_follows_job_status(tmp_path, faults, rc, status):
    """The training CLI exits 0 only when its job SUCCEEDED: five seeded
    faults, all at step 1, outlast the default three attempts. Its compile
    cache goes where JAX_COMPILATION_CACHE_DIR says."""
    cache = tmp_path / "jax_cache"
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke", "--steps", "2",
         "--workers", "1", "--ps", "0", "--batch-size", "2", "--seq-len", "16",
         "--ckpt-dir", str(tmp_path / "ck"),
         "--chaos-random-faults", str(faults)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == rc, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert f'"status": "{status}"' in proc.stdout
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert "no TPU found" in out.err and '"ok"' not in out.out
