"""The ML programs TonY spawns as child processes.

``make_train_program`` builds a TonY-compatible callable that runs a real JAX
training loop (model/optimizer/data/checkpointing from this repo) under
whatever cluster spec the AM hands it.

Single-process adaptation: the chief worker drives the jit-compiled SPMD
step over the full local mesh; other tasks execute the
launch/rendezvous/heartbeat protocol and wait — in a real multi-host
deployment every rank would call ``jax.distributed.initialize`` and drive the
same program.
"""
from __future__ import annotations

import functools
import json
import math
import queue
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.checkpoint import AsyncCheckpointer
from repro.configs.base import ModelConfig
from repro.core import tracing
from repro.core.cluster_spec import spec_task_counts
from repro.core.task_executor import JobContext
from repro.data import PrefetchingLoader, make_dataset
from repro.distributed.sharding import to_shardings
from repro.distributed.steps import init_train_state, make_train_fn
from repro.optim import AdamWConfig


def _local_mesh(strategy: str):
    devs = np.array(jax.devices())
    n = len(devs)
    # split devices into (data, model); prefer square-ish
    model = 1
    for m in (8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def _bytes_per_device(template, shardings) -> int:
    """Bytes of the state that one device holds: each leaf's shard, at the
    largest shard shape where a split is uneven."""
    sizes = jax.tree.map(
        lambda leaf, sh: math.prod(sh.shard_shape(leaf.shape))
        * leaf.dtype.itemsize, template, shardings)
    return int(sum(jax.tree.leaves(sizes)))


def _put_restored(state, shardings, **attrs):
    """``jax.device_put`` of a restored host state onto its shardings.

    Returns once the transfer is issued, as a plain ``device_put`` does, so
    that the first step's retrace and executable load overlap the transfer.
    The ``ckpt.restore.put`` span runs on a watcher thread from the call
    until the bytes are on the device."""
    issued: queue.Queue = queue.Queue(maxsize=1)

    def put() -> None:
        with tracing.span("ckpt.restore.put", **attrs):
            try:
                arrays = jax.device_put(state, shardings)
            except Exception as e:  # noqa: BLE001 - re-raised by the caller
                issued.put(e)
                return
            issued.put(arrays)
            jax.block_until_ready(arrays)

    threading.Thread(target=put, name="ckpt-restore-put", daemon=True).start()
    arrays = issued.get()
    if isinstance(arrays, Exception):
        raise arrays
    return arrays


def make_train_program(cfg: ModelConfig, *, steps: int, batch_size: int,
                       seq_len: int, ckpt_dir: str, ckpt_every: int = 10,
                       strategy: str = "fsdp_tp",
                       lr: float = 1e-3,
                       data_kind: str = "synthetic",
                       data_path: str | None = None,
                       data_seed: int = 0,
                       on_step: Callable[[int, dict], None] | None = None):
    """Returns an MLProgram: the chief worker trains ``steps`` steps, the
    other tasks keep pace with it.

    The chief saves every ``ckpt_every`` steps and at the last step through
    an ``AsyncCheckpointer``: the save snapshots the state and a background
    writer commits it, publishing ``ctx.shared["ckpt_step"]`` only after the
    commit, so a relaunch never resumes from an uncommitted step. A
    ``PrefetchingLoader`` builds the next two batches while the device runs
    the step. On a normal exit the chief flushes the writer, so the last
    checkpoint has committed before the task succeeds. Faults come from the
    cluster's chaos plan through ``ctx.step`` and the writer's hook.
    ``on_step(step, metrics)`` is called after each step's loss is on the
    host."""

    def program(env: dict[str, str], ctx: JobContext) -> int:
        t_entry = time.monotonic()
        task_type = env["TASK_TYPE"]
        index = int(env["TASK_INDEX"])
        task_id = f"{task_type}:{index}"
        spec = json.loads(env["CLUSTER_SPEC"])
        attempt = int(ctx.shared.get("attempt", 1))
        # a speculative backup copy joins an already-formed gang: it must
        # not touch the rendezvous barrier (the gang already passed it) and
        # keys its shared-dict entries under the copy-suffixed exec id
        speculative = env.get("SPECULATIVE") == "1"
        exec_id = task_id + "#1" if speculative else task_id

        # identify ourselves to the barrier so a chaos PARTITION window
        # blocks this endpoint's rendezvous (it can't reach its peers)
        if not speculative:
            with tracing.span("task.rendezvous", exec_id=exec_id,
                              attempt=attempt):
                joined = ctx.rendezvous(timeout=60.0, exec_id=exec_id,
                                        attempt=attempt)
            if not joined:
                return 3  # cancelled before the job formed

        worker_types = [t for t in ("worker", "chief") if t in spec]
        chief_type = worker_types[0] if worker_types else sorted(spec)[0]
        is_chief = task_type == chief_type and index == 0

        rc = 0
        if is_chief:
            rc = _chief_train_loop(env, ctx, attempt, exec_id, t_entry)
        else:
            # non-chief: stay alive for the duration of the job ("the ML
            # framework's distributed protocol" is collapsed into-process),
            # advancing its own step counter at the gang's pace through the
            # chaos-gated ctx.step hook — so a SLOW_STEP fault makes this
            # worker visibly lag the gang median (straggler detection) even
            # though only the chief runs the real training loop
            my_step = -1
            while not ctx.cancel.is_set() and not ctx.shared.get("train_done"):
                lead = max((v for k, v in ctx.progress.items()
                            if k != exec_id), default=-1)
                if my_step < lead:
                    my_step += 1
                    ctx.step(exec_id, attempt, my_step)
                else:
                    time.sleep(0.002)
        if not speculative:
            ctx.shared["train_done"] = True
            ctx.rendezvous(timeout=30.0, exec_id=exec_id, attempt=attempt)
        return rc

    def _chief_train_loop(env, ctx: JobContext, attempt: int, exec_id: str,
                          t_entry: float) -> int:
        # the spans of one attempt tile the chief's timeline from entry to
        # each step: task.rendezvous, chief.build (with ckpt.restore.read
        # inside it on a resume), then one train.step each
        with tracing.span("chief.build", exec_id=exec_id,
                          attempt=attempt) as build:
            mesh = _local_mesh(strategy)
            # elastic resize: shard for the gang that ACTUALLY launched, not
            # the one the config asked for. A degraded attempt scales the
            # global batch down proportionally (rounded to a multiple of the
            # mesh's data axis so sharding stays valid); a full-size attempt
            # keeps the configured batch byte-for-byte.
            spec = json.loads(env["CLUSTER_SPEC"])
            counts = spec_task_counts(spec)
            targets = ctx.shared.get("target_counts") or {}
            my_type = env["TASK_TYPE"]
            n_actual = counts.get(my_type, 1)
            n_target = targets.get(my_type, n_actual)
            global_batch = batch_size
            if 0 < n_actual < n_target:
                data_ax = int(mesh.shape["data"])
                scaled = max(1, batch_size * n_actual // n_target)
                global_batch = max(data_ax, (scaled // data_ax) * data_ax)
            data = PrefetchingLoader(
                make_dataset(data_kind, global_batch, seq_len, cfg.vocab_size,
                             path=data_path, seed=data_seed), depth=2)

            def on_commit(ckpt_step: int, path: str, duration_s: float,
                          nbytes: int) -> None:
                # the resume contract's publish point: ONLY after the atomic
                # rename landed (this runs on the writer thread), so the AM
                # can never resume from an uncommitted step
                ctx.shared["ckpt_step"] = ckpt_step
                if ctx.events is not None:
                    ctx.events.emit(f"ckpt:{exec_id}", "ckpt_committed",
                                    step=ckpt_step, duration_s=duration_s,
                                    bytes=nbytes, attempt=attempt)

            ckpt = AsyncCheckpointer(
                ckpt_dir, on_commit=on_commit,
                chaos_hook=lambda s: ctx.chaos.check_ckpt_write(
                    exec_id, attempt, s))
            # graceful teardown paths (executor exit, mid-attempt shed)
            # drain the writer so committed work is never lost
            ctx.register_flusher(ckpt.flush)
            with jax.set_mesh(mesh):
                train_fn, state_pspecs = make_train_fn(
                    cfg, mesh, strategy,
                    opt=AdamWConfig(lr=lr, weight_decay=0.0))
                # the state is born sharded: never gathered whole on one device
                shardings = to_shardings(state_pspecs, mesh)
                init = jax.jit(functools.partial(init_train_state, cfg),
                               out_shardings=shardings)
                rng = jax.random.PRNGKey(0)
                # checkpoint-aware recovery: prefer the AM's resume_step (the
                # deepest checkpoint a previous attempt committed), fall back
                # to whatever this directory holds (resume across
                # submissions), and only then cold-start from step 0
                start = 0
                state = None
                template = jax.eval_shape(init, rng)
                target = ctx.shared.get("resume_step")
                if target is None:
                    target = ckpt.latest_step()
                if target is not None:
                    try:
                        state = ckpt.restore(template, int(target))
                    except (FileNotFoundError, KeyError, ValueError, OSError):
                        target = ckpt.latest_step()
                        if target is not None:
                            state = ckpt.restore(template, int(target))
                state = (init(rng) if state is None else _put_restored(
                    state, shardings, exec_id=exec_id, attempt=attempt))
            if target is not None:
                data.load_state_dict({"step": int(target)})
                start = int(target)
                ctx.shared["ckpt_step"] = start
                ctx.shared.setdefault("restarts", []).append(
                    {"attempt": attempt, "restored_step": start})
            # set last, so that the spans inside do not inherit them
            build.attrs.update(
                chips=mesh.devices.size, mesh=tuple(mesh.devices.shape),
                state_bytes_per_device=_bytes_per_device(template, shardings))

        losses = ctx.shared.setdefault("loss_history", [])
        with jax.set_mesh(mesh):
            try:
                for step in range(start, steps):
                    if ctx.cancel.is_set():
                        return 143
                    with tracing.span("train.step", exec_id=exec_id,
                                      attempt=attempt, step=step):
                        # records progress for straggler detection + runs
                        # the chaos hooks (which may delay or kill this step)
                        with tracing.span("train.chaos_hook"):
                            ctx.step(exec_id, attempt, step)
                        with tracing.span("train.next_batch"):
                            host_batch = data.next_batch()
                        with tracing.span("train.h2d"):
                            batch = {k: jnp.asarray(v)
                                     for k, v in host_batch.items()}
                        with tracing.span("train.dispatch"):
                            state, metrics = train_fn(state, batch)
                        with tracing.span("train.loss_wait"):
                            loss = float(metrics["loss"])
                        losses.append((step, loss))
                        if on_step:
                            with tracing.span("train.on_step"):
                                on_step(step, {k: float(v)
                                               for k, v in metrics.items()})
                        if (step + 1) % ckpt_every == 0 or step + 1 == steps:
                            # snapshot + hand off; the writer publishes
                            # ckpt_step after commit. A deferred writer error
                            # (e.g. a chaos kill mid-write) re-raises here.
                            with tracing.span("ckpt.save"):
                                ckpt.save(state, step + 1)
                # normal exit: surface any deferred writer error and make
                # sure the final checkpoint committed before succeeding
                ckpt.flush()
            finally:
                ckpt.close()
                data.close()
        report = {
            "steps": float(steps),
            "final_loss": losses[-1][1] if losses else float("nan"),
            "world_size": float(sum(counts.values())),
            "global_batch": float(global_batch),
        }
        stats = [d.memory_stats() for d in mesh.devices.flat]
        if all(st and "peak_bytes_in_use" in st for st in stats):
            report["peak_memory_mb"] = float(
                max(st["peak_bytes_in_use"] for st in stats) / 1e6)
        # seconds per span name over this attempt: where the chief's time
        # went (nested spans are counted inside their parents too)
        for sp in tracing.spans(since=t_entry):
            if (sp.attrs.get("exec_id") == exec_id
                    and sp.attrs.get("attempt") == attempt):
                key = f"span_s:{sp.name}"
                report[key] = report.get(key, 0.0) + sp.duration
        ctx.shared[f"metrics:{exec_id}"] = report
        return 0

    return program
