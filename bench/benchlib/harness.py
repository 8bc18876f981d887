"""One run of one cell: set-up, job start, the window, the check, the line.

``run_cell`` is the whole run after the device check; ``bench/run.py`` is its
command line. Tests call it on the CPU at a tiny size.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import check, device, flops, traffic as gen
from benchlib.job import CompileLog, Recorder, StateReader
from benchlib.record import RunRecord
from benchlib.spec import (ROOT, Cell, SpecError, load_json, metric_reader,
                           reference_module)

OUT_DIR = ROOT / "chiprun_out" / "bench"
FIRST_STEP_TIMEOUT_S = 300.0
RESUME_TIMEOUT_S = 150.0
JOB_END_TIMEOUT_S = 120.0
# The program's optimizer, as make_train_program builds it: AdamWConfig's
# betas, eps and clip, its default lr of 1e-3 and weight_decay=0.0.
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 1.0}


def program_config(config: dict):
    """The program's own configuration for this file: its registry entry,
    cut as ``reduced`` says. Any width that disagrees with the file is an
    error: the file holds the sizes as they are run."""
    from repro.configs import get_config

    cfg = get_config(config["program_config"]).replace(
        num_layers=int(config["num_hidden_layers"]))
    have = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "tie_word_embeddings": cfg.tie_embeddings,
            "qk_norm": cfg.use_qk_norm}
    wrong = {k: (v, config[k]) for k, v in have.items()
             if k in config and v != config[k]}
    if wrong:
        raise SpecError(f"program config {cfg.name} differs from "
                        f"{config['name']}: {wrong}")
    return cfg


def enable_compile_cache() -> str:
    """The program's persistent compile cache, where the command line put
    it (``JAX_COMPILATION_CACHE_DIR``, inside the checkout), keeping every
    program however fast it compiled, so that only a checkout's first run
    compiles."""
    from repro.launch.compile_cache import enable_compile_cache as enable

    cache_dir = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def _warm_up(cfg, cell: Cell, tokens: np.ndarray, reader: StateReader):
    """Build the init and step programs exactly as the job's chief builds
    them and run each once, so that the job finds both in the persistent
    compile cache; likewise the state reader's programs. Frees it all."""
    import functools

    from repro.distributed.sharding import to_shardings
    from repro.distributed.steps import init_train_state, make_train_fn
    from repro.launch import programs
    from repro.optim import AdamWConfig

    mesh = programs._local_mesh(cell.traffic["strategy"])
    with jax.set_mesh(mesh):
        train_fn, pspecs = make_train_fn(
            cfg, mesh, cell.traffic["strategy"],
            opt=AdamWConfig(lr=OPT["lr"], weight_decay=0.0))
        init = jax.jit(functools.partial(init_train_state, cfg),
                       out_shardings=to_shardings(pspecs, mesh))
        state = init(jax.random.PRNGKey(0))
        batch = {k: jnp.asarray(v)
                 for k, v in gen.batch_at(tokens, cell.traffic, 0).items()}
        state, metrics = train_fn(state, batch)
        float(metrics["loss"])
        reader.warm(state)
        del state, metrics, batch
    gc.collect()


def reference_run(cell: Cell, tokens: np.ndarray, steps: int,
                  matmul: str = "f32", rows: slice = slice(None),
                  opt: dict = OPT) -> dict:
    ref = reference_module(cell.config)
    batches = [{k: v[rows] for k, v in
                gen.batch_at(tokens, cell.traffic, s).items()}
               for s in range(steps)]
    return ref.train_steps(cell.config, batches, opt, matmul=matmul)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, fault: str | None = None,
             log=print) -> dict:
    from repro.core import (EventLog, FaultInjector, FaultKind, FaultPlan,
                            FaultSpec, TonYClient, YarnLikeBackend,
                            job_spec_from_props, make_cluster)
    from repro.launch import programs

    t = cell.traffic
    log(f"compile cache: {enable_compile_cache()}; "
        f"{time.monotonic() - t_start:.3f} s after start")
    cfg = program_config(cell.config)
    compiled = CompileLog()
    limits = load_json(ROOT / "bench" / "limits" / f"{cell.name}.json")
    compared = int(limits["compared_steps"])
    tokens = gen.make_tokens(t, cell.config["vocab_size"], seed)
    work = Path(tempfile.mkdtemp(prefix="bench-"))
    try:
        data_path = work / "tokens.bin"
        tokens.tofile(data_path)
        reader = StateReader(compared, fault)
        t_warm = time.monotonic()
        _warm_up(cfg, cell, tokens, reader)
        log(f"warm-up: {time.monotonic() - t_warm:.3f} s")
        setup_s = time.monotonic() - t_start
        log(f"setup_s {setup_s:.3f}")

        rec = Recorder()
        events = EventLog()
        kill_at = gen.kill_step(t)
        plan = FaultPlan(seed=0)
        if kill_at is not None:
            plan = plan.add(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                      attempt=1, at_step=kill_at))
        rm = make_cluster(event_log=events,
                          chaos=FaultInjector(plan, events=events))
        client = TonYClient(YarnLikeBackend(rm), events=events)
        job = job_spec_from_props(gen.job_props(t, cell.chips, cell.name))
        prog = programs.make_train_program(
            cfg, steps=gen.NO_END, batch_size=t["batch"], seq_len=t["seq_len"],
            ckpt_dir=str(work / "ckpt"),
            ckpt_every=t.get("ckpt_every") or gen.NO_SAVE,
            strategy=t["strategy"], lr=OPT["lr"], data_kind="file",
            data_path=str(data_path), on_step=rec.on_step)

        original = programs.make_train_fn

        def make_train_fn(*a, **kw):
            fn, pspecs = original(*a, **kw)
            return reader.wrap(fn, rec.attempt), pspecs

        programs.make_train_fn = make_train_fn
        try:
            t_submit = time.monotonic()
            handle = client.submit(job, rec.wrap_program(prog))
            if not rec.wait_for(lambda: rec.steps, FIRST_STEP_TIMEOUT_S):
                raise RuntimeError("the job completed no step in "
                                   f"{FIRST_STEP_TIMEOUT_S} s")
            t_open = rec.steps[0].t
            t_close = t_open + seconds
            trace_dir = None
            if trace:
                trace_dir = _traced_window(t, rec, t_close, cell, seed)
            _sleep_until(t_close)
            if kill_at is not None:
                rec.wait_for(lambda: rec.first_step_of(2) is not None,
                             RESUME_TIMEOUT_S)
            rec.stop()
            result = handle.wait(JOB_END_TIMEOUT_S)
        finally:
            programs.make_train_fn = original
            rec.stop()
            _join_abandoned_programs()
        peak_bytes = device.memory_peak_bytes(devices)
        log(f"job {result.final_status} after {len(result.attempts)} "
            f"attempt(s), resumed {result.resumed_attempts}")

        unplanned = _unplanned_failures(result, kill_at)
        for u in unplanned:
            log(f"unplanned failure: {u}")
        log("timeline after the window opened (s): "
            f"entries {[(a, round(t - t_open, 3)) for a, t in rec.entries.items()]} "
            f"kills {[round(e.ts - t_open, 3) for e in events.of_kind('chaos_injected')]} "
            "commits (step, at, write s) "
            f"{[(e.payload.get('step'), round(e.ts - t_open, 3), round(e.payload.get('duration_s', 0), 3)) for e in events.of_kind('ckpt_committed')]}")
        record = RunRecord(
            cell=cell, seconds=seconds, setup_s=setup_s, t_submit=t_submit,
            t_open=t_open, t_close=t_close, steps=list(rec.steps),
            entries=dict(rec.entries),
            chaos=[e.ts for e in events.of_kind("chaos_injected")],
            commits=[{"t": e.ts, **e.payload}
                     for e in events.of_kind("ckpt_committed")],
            compiles=list(compiled.compiles),
            flops_per_step=flops.train_step_flops(cell.config, t["batch"],
                                                  t["seq_len"]),
            tokens_per_step=gen.tokens_per_step(t),
            peak_flops=device.peaks(devices[0].device_kind)["bf16_flops_per_s"]
            if devices[0].platform == "tpu" else math.nan,
            chips=cell.chips)
        gaps = sorted(record.intervals(), key=lambda g: -g[1])
        if gaps:
            log(f"step intervals in the window: {len(gaps)}, median "
                f"{np.median([g for _, g in gaps]):.4f} s, longest "
                f"{[(s, round(g, 4)) for s, g in gaps[:6]]}")
        in_window = [c for c in record.compiles if t_open < c[0] <= t_close
                     and not _in_start(record, c[0])]
        log(f"compiles inside the window outside an attempt's start: "
            f"{len(in_window)} {[(c[2], round(c[1], 3), c[3]) for c in in_window]}")
        if trace_dir is not None:
            from benchlib.trace import reduce_dir
            record.trace = reduce_dir(trace_dir)

        job_view = _job_view(rec, reader)
        del result, handle, prog, reader
        gc.collect()

        # the reference runs last: after the window, the memory reading and
        # the job's teardown, so it neither slows the job nor sets the peak
        t_ref = time.monotonic()
        ref = reference_run(cell, tokens, compared)
        log(f"reference: {time.monotonic() - t_ref:.1f} s")
        compared_numbers = check.numbers(job_view, ref)
        log(f"diagnostics: {json.dumps(check.diagnostics(job_view, ref))}")
        if kill_at is not None:
            rg = check.restore_gap(record.steps)
            if rg is None:
                unplanned.append("no step was recomputed after the resume")
            else:
                compared_numbers["restore_gap"] = rg
    finally:
        shutil.rmtree(work, ignore_errors=True)

    metrics = (cell.per_layer if trace else cell.end_to_end)
    values = {}
    for m in metrics:
        v = metric_reader(m.name)(record)
        if v is not None:
            values[m.name] = {"value": v, "unit": m.unit}
    window_steps = record.window_steps()
    nonfinite = sum(1 for s in window_steps if not math.isfinite(s.loss))
    checks = {name: {"value": v, "limit": limits["limits"][name], "at": at}
              for name, (v, at) in compared_numbers.items()}
    correct = (not unplanned and nonfinite == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = peak_bytes
    if trace and record.trace is not None:
        dev["busy_s"] = record.trace["busy_s"]
        dev["window_s"] = record.trace["window_s"]
    line = {"correct": correct, "attempted": len(window_steps),
            "failed": nonfinite + len(unplanned), "metrics": values,
            "device": dev}
    if trace and record.trace is not None:
        line["breakdown"] = record.trace["breakdown"]
    line["checks"] = checks
    return line


def _join_abandoned_programs() -> None:
    """Wait for the chief's program thread. On cancel the executor abandons
    its child thread (the stand-in for killing a container), which finishes
    its step in flight and then returns; its prefetcher and checkpoint
    writer end with it."""
    deadline = time.monotonic() + JOB_END_TIMEOUT_S
    for th in threading.enumerate():
        if th.name.startswith(("ml-", "prefetch-loader", "ckpt-writer")):
            th.join(max(0.0, deadline - time.monotonic()))
            if th.is_alive():
                raise RuntimeError(f"thread {th.name} did not end")


def _sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.5))


def _traced_window(t: dict, rec: Recorder, t_close: float, cell: Cell,
                   seed: int) -> Path:
    """Profile ``trace_seconds`` of the window from the completion of step
    ``trace_after_step`` of the first attempt (a step count, so that the
    trace holds the same work however fast the steps run) into
    ``chiprun_out/bench/<cell>-<seed>``. The span ``trace.WINDOW_SPAN``
    marks the stretch the reduction reads."""
    from benchlib.trace import WINDOW_SPAN

    out = OUT_DIR / f"{cell.name}-{seed}"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    after = int(t["trace_after_step"])
    rec.wait_for(lambda: any(s.step >= after for s in rec.steps),
                 max(0.0, t_close - time.monotonic()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # JAX's host events and ours only
    jax.profiler.start_trace(str(out), profiler_options=options)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        _sleep_until(min(time.monotonic() + t["trace_seconds"], t_close))
    jax.profiler.stop_trace()
    return out


def _in_start(record: RunRecord, ts: float) -> bool:
    """Whether ts lies between an attempt's program entry and its first
    step, where a relaunched attempt loads its programs."""
    for attempt, entry in record.entries.items():
        first = record.first_step(attempt)
        if entry <= ts <= (first if first is not None else math.inf):
            return True
    return False


def _unplanned_failures(result, kill_at: int | None) -> list[str]:
    """Every task failure other than the planned kill of the chief in
    attempt 1 and the bench's stop of the last attempt."""
    last = len(result.attempts)
    out = []
    for key, diag in sorted(result.diagnostics.items()):
        attempt = int(key.split("/")[0][1:])
        text = diag.describe()
        planned_kill = (kill_at is not None and attempt == 1
                        and "ChaosKill" in text)
        # exit 143 is the AM's teardown: of the last attempt by the
        # bench's stop, of attempt 1 after the planned kill
        torn_down = "exit status 143" in text and (
            attempt == last or (kill_at is not None and attempt == 1))
        if not (planned_kill or torn_down):
            out.append(f"{key}: {text}")
    if kill_at is not None and last < 2:
        out.append("the planned kill did not relaunch the job")
    return out


def _job_view(rec: Recorder, reader: StateReader) -> dict:
    first = sorted((s for s in rec.steps if s.attempt == 1),
                   key=lambda s: s.step)
    losses = [s.loss for s in first[:reader.compared_steps]]
    if (reader.names is None or reader.moment0 is None
            or reader.change is None
            or len(losses) < reader.compared_steps):
        raise RuntimeError("the job ended before its compared steps")
    names = reader.names
    moment0 = dict(zip(names, np.asarray(reader.moment0).tolist()))
    return {"losses": losses,
            "grad0_norms": check.program_grad0(moment0, first[0].grad_norm,
                                               OPT["b1"], OPT["clip"]),
            "change_norms": dict(zip(names,
                                     np.asarray(reader.change).tolist()))}


def print_checks(line: dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"({c['at']})", file=sys.stderr)
    sys.stderr.flush()


def dumps(line: dict) -> str:
    return json.dumps(line, allow_nan=False)

