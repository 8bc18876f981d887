"""The span API (repro.core.tracing) and the spans a training job emits."""
import functools
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (EventLog, FaultInjector, FaultKind, FaultPlan,
                        FaultSpec, TonYClient, YarnLikeBackend,
                        job_spec_from_props, make_cluster, tracing)


def test_nested_spans_carry_parent_ids_and_inherit_attrs():
    t0 = time.monotonic()
    with tracing.span("outer", exec_id="worker:0", attempt=1):
        with tracing.span("outer.inner", step=3):
            with tracing.span("outer.inner.leaf", bytes=8):
                pass
        with tracing.span("outer.sibling"):
            pass
    got = {s.name: s for s in tracing.spans(since=t0)}
    outer, inner = got["outer"], got["outer.inner"]
    leaf, sibling = got["outer.inner.leaf"], got["outer.sibling"]
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert leaf.parent_id == inner.span_id
    assert sibling.parent_id == outer.span_id
    assert leaf.attrs == {"exec_id": "worker:0", "attempt": 1, "step": 3,
                          "bytes": 8}
    assert sibling.attrs == {"exec_id": "worker:0", "attempt": 1}
    assert outer.start <= inner.start <= leaf.start <= leaf.end \
        <= inner.end <= sibling.start <= sibling.end <= outer.end
    assert {s.thread for s in got.values()} == {
        threading.current_thread().name}
    assert [s.name for s in tracing.spans(prefix="outer.inner",
                                          since=t0)] == [
        "outer.inner.leaf", "outer.inner"]
    assert tracing.spans(since=time.monotonic()) == []


def test_a_span_closed_by_an_exception_is_recorded():
    t0 = time.monotonic()
    with pytest.raises(ValueError):
        with tracing.span("raises"):
            raise ValueError("boom")
    with tracing.span("after"):
        pass
    got = {s.name: s for s in tracing.spans(since=t0)}
    assert got["after"].parent_id is None    # the stack was unwound


def test_buffer_keeps_the_newest_max_spans():
    extra = 10
    for i in range(tracing.MAX_SPANS + extra):
        with tracing.span("fill", i=i):
            pass
    kept = tracing.spans()
    assert len(kept) == tracing.MAX_SPANS
    assert [s.attrs["i"] for s in (kept[0], kept[-1])] == [
        extra, tracing.MAX_SPANS + extra - 1]


def test_concurrent_threads_keep_their_own_parents():
    threads, rounds = 16, 200
    t0 = time.monotonic()
    start = threading.Barrier(threads)

    def work(k):
        start.wait(10)
        for r in range(rounds):
            with tracing.span("stress.outer", k=k, r=r):
                with tracing.span("stress.inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(k,), name=f"stress-{k}")
                for k in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(60)
        assert not any(th.is_alive() for th in pool)
    finally:
        sys.setswitchinterval(old)
    got = tracing.spans(prefix="stress.", since=t0)
    assert len(got) == 2 * threads * rounds
    assert len({s.span_id for s in got}) == len(got)
    outers = {s.span_id: s for s in got if s.name == "stress.outer"}
    for s in got:
        if s.name == "stress.inner":
            parent = outers[s.parent_id]
            assert s.thread == parent.thread == f"stress-{parent.attrs['k']}"
            assert s.attrs == parent.attrs
            assert parent.start <= s.start <= s.end <= parent.end


def test_spans_show_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("traced.outer"):
            with tracing.span("traced.inner"):
                jax.block_until_ready(jax.numpy.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    lines = {line.name: [ev.name for ev in line.events]
             for plane in host for line in plane.lines}
    mine = [names for names in lines.values() if "traced.outer" in names]
    assert len(mine) == 1 and "traced.inner" in mine[0]


CFG_KW = dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
              head_dim=32, d_ff=128, vocab_size=128, max_position=64)
STEP_CHILDREN = ["train.chaos_hook", "train.next_batch", "train.h2d",
                 "train.dispatch", "train.loss_wait", "train.on_step"]


def test_kill_job_emits_each_attempts_spans_in_order(tmp_path):
    """A tiny job whose chief is killed at step 6 and resumes from the
    step-4 checkpoint: each attempt's chief tiles its timeline with
    task.rendezvous, chief.build and one train.step per step, and only the
    relaunched attempt restores."""
    from repro.checkpoint import tree_nbytes
    from repro.configs import get_config
    from repro.distributed.steps import init_train_state
    from repro.launch.programs import make_train_program

    steps, every, kill = 10, 4, 6
    plan = FaultPlan(seed=0).add(FaultSpec(
        FaultKind.KILL_TASK, task="worker:0", attempt=1, at_step=kill))
    events = EventLog()
    rm = make_cluster(event_log=events,
                      chaos=FaultInjector(plan, events=events))
    job = job_spec_from_props({"tony.application.name": "spans",
                               "tony.application.max-attempts": "2",
                               "tony.worker.instances": "2",
                               "tony.worker.memory": "1024"})
    cfg = get_config("tony-paper-mlp").replace(**CFG_KW)
    prog = make_train_program(
        cfg, steps=steps,
        batch_size=4, seq_len=16, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=every, on_step=lambda s, m: None)
    t0 = time.monotonic()
    res = TonYClient(YarnLikeBackend(rm)).run_and_wait(job, prog, timeout=300)
    assert res.succeeded and res.resumed_attempts == {2: every}

    got = tracing.spans(since=t0)
    kids: dict = {}
    for s in sorted(got, key=lambda s: s.span_id):
        kids.setdefault(s.parent_id, []).append(s)

    def names(parent):
        return [s.name for s in kids.get(parent.span_id, [])]

    puts = [s for s in got if s.name == "ckpt.restore.put"]
    for attempt, first in ((1, 0), (2, every)):
        mine = [s for s in kids[None] if s.attrs.get("exec_id") == "worker:0"
                and s.attrs.get("attempt") == attempt]
        chief = mine[0].thread
        top = [s for s in mine if s.thread == chief]
        last = kill if attempt == 1 else steps - 1
        assert [s.name for s in top] == (
            ["task.rendezvous", "chief.build"]
            + ["train.step"] * (last - first + 1))
        build = top[1]
        assert names(build) == ([] if attempt == 1 else ["ckpt.restore.read"])
        # the put runs on its own thread from inside chief.build until the
        # bytes are on the device, overlapping the first step's dispatch
        assert [s for s in mine if s.thread != chief] == (
            [] if attempt == 1 else puts)
        if attempt == 2:
            [put] = puts
            assert put.thread == "ckpt-restore-put"
            assert build.start <= put.start <= build.end
            # the whole state, one raw file per leaf
            [read] = kids[build.span_id]
            state = jax.eval_shape(functools.partial(init_train_state, cfg),
                                   jax.random.PRNGKey(0))
            assert read.attrs["layout"] == "leaves"
            assert read.attrs["files"] == len(jax.tree.leaves(state))
            assert read.attrs["bytes"] == tree_nbytes(jax.tree.map(
                lambda x: np.empty(x.shape, x.dtype), state))
            assert read.attrs["mapped"] == read.attrs["bytes"]
        for step_span, step in zip(top[2:], range(first, last + 1)):
            assert step_span.attrs["step"] == step
            if attempt == 1 and step == kill:       # killed in its hook
                assert names(step_span) == ["train.chaos_hook"]
                continue
            saves = (step + 1) % every == 0 or step + 1 == steps
            assert names(step_span) == STEP_CHILDREN + ["ckpt.save"] * saves
            for save in kids.get(step_span.span_id, [])[len(STEP_CHILDREN):]:
                assert names(save) == ["ckpt.snapshot", "ckpt.handoff"]
                [snap] = kids[save.span_id][:1]
                assert snap.attrs["bytes"] > 0 and snap.attrs["step"] == step
    restores = [s for s in got if s.name.startswith("ckpt.restore.")]
    assert {s.attrs["attempt"] for s in restores} == {2}

    # the chief's report: span totals of its attempt, no fabricated numbers
    assert set(res.metrics) == {"a2/worker:0"}
    report = res.metrics["a2/worker:0"]
    assert report["steps"] == float(steps)
    assert "train_seconds" not in report
    assert "peak_memory_mb" not in report      # the CPU gives no peak
    step_s = sum(s.duration for s in got if s.name == "train.step"
                 and s.attrs["attempt"] == 2)
    assert report["span_s:train.step"] == pytest.approx(step_s)
    assert report["span_s:ckpt.restore.read"] > 0


FOUR_DEVICE_JOB = r"""
import functools, json
import jax
from repro.configs import get_config
from repro.core import (TonYClient, YarnLikeBackend, job_spec_from_props,
                        make_cluster, tracing)
from repro.distributed.sharding import to_shardings
from repro.distributed.steps import init_train_state, make_train_fn
from repro.launch.programs import _local_mesh, make_train_program

cfg = get_config("deepseek-coder-33b").replace(
    num_layers=2, d_model=64, num_heads=8, num_kv_heads=4, head_dim=16,
    d_ff=128, vocab_size=256)
job = job_spec_from_props({"tony.application.name": "four",
                           "tony.application.max-attempts": "1",
                           "tony.worker.instances": "4",
                           "tony.worker.memory": "1024"})
prog = make_train_program(cfg, steps=2, batch_size=2, seq_len=16,
                          ckpt_dir=CKPT, ckpt_every=10**9)
res = TonYClient(YarnLikeBackend(make_cluster())).run_and_wait(
    job, prog, timeout=300)
[build] = tracing.spans(prefix="chief.build")
# what device 0 holds of the state, read from the arrays' own shards
mesh = _local_mesh("fsdp_tp")
with jax.set_mesh(mesh):
    _, pspecs = make_train_fn(cfg, mesh)
    state = jax.jit(functools.partial(init_train_state, cfg),
                    out_shardings=to_shardings(pspecs, mesh))(
        jax.random.PRNGKey(0))
d0 = mesh.devices.flat[0]
held = sum(s.data.nbytes for leaf in jax.tree.leaves(state)
           for s in leaf.addressable_shards if s.device == d0)
total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
print(json.dumps({"ok": res.succeeded, "attrs": build.attrs, "held": held,
                  "total": total}))
"""


def test_chief_build_names_the_mesh_and_the_state_per_device(tmp_path):
    """A tiny four-worker job on four CPU devices, in a process of its own
    (this one holds a one-device backend): ``chief.build`` carries the
    chips, the (data, model) mesh and the bytes of the state that one
    device holds."""
    import json
    import os
    import subprocess
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = f"CKPT = {str(tmp_path / 'ck')!r}\n" + FOUR_DEVICE_JOB
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["ok"]
    attrs = got["attrs"]
    assert attrs["chips"] == 4 and attrs["mesh"] == [1, 4]
    assert attrs["state_bytes_per_device"] == got["held"]
    assert got["total"] / 4 <= got["held"] < got["total"]
