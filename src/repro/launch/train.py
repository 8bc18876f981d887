"""Training driver: submits a distributed training job through the full TonY
path (client -> RM -> AM -> executors -> JAX train loop).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 50 --batch-size 8 --seq-len 64 [--workers 2 --ps 1]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import (
    EventLog,
    FaultInjector,
    FaultKind,
    FaultPlan,
    FaultSpec,
    JobHistoryServer,
    MetricsAnalyzer,
    NodeHealthTracker,
    SpeculationPolicy,
    TonYClient,
    YarnLikeBackend,
    format_failure_report,
    job_spec_from_props,
    make_cluster,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.programs import make_train_program


def build_job(name: str, workers: int, ps: int, gpus_per_worker: int = 1,
              min_workers: int = 0):
    props = {
        "tony.application.name": name,
        "tony.worker.instances": str(workers),
        "tony.worker.memory": "8192",
        "tony.worker.vcores": "4",
        "tony.worker.gpus": str(gpus_per_worker),
        "tony.worker.node-label": "gpu",
    }
    if min_workers > 0:
        # elastic gang: the AM may run degraded down to this many workers
        props["tony.worker.min-instances"] = str(min_workers)
    if ps > 0:
        props.update({
            "tony.ps.instances": str(ps),
            "tony.ps.memory": "4096",
            "tony.ps.vcores": "2",
            "tony.ps.node-label": "highmem",
        })
    return job_spec_from_props(props)


def main(argv: list[str] | None = None) -> int:
    """Runs one job; returns the exit code (0 only when it SUCCEEDED)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tony-paper-mlp", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--min-workers", type=int, default=0,
                    help="elastic gang floor (tony.worker.min-instances); "
                         "0 = rigid: exactly --workers or the attempt fails")
    ap.add_argument("--ps", type=int, default=1)
    ap.add_argument("--strategy", default="fsdp_tp")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    chaos = ap.add_argument_group(
        "chaos", "deterministic fault injection (core/chaos.py)")
    chaos.add_argument("--chaos-seed", type=int, default=1234,
                       help="seed identifying the fault plan in events/logs")
    chaos.add_argument("--chaos-kill-step", type=int, default=None,
                       help="kill the chief worker at this step (once)")
    chaos.add_argument("--chaos-oom-step", type=int, default=None,
                       help="OOM the chief worker at this step (once)")
    chaos.add_argument("--chaos-kill-ckpt-write", type=int, default=None,
                       metavar="STEP",
                       help="kill the chief inside the async checkpoint "
                            "writer while it writes this step (once) — the "
                            "relaunch must resume from the previous "
                            "committed step")
    chaos.add_argument("--chaos-random-faults", type=int, default=0,
                       help="generate N seeded random kill/OOM faults")
    chaos.add_argument("--blacklist-threshold", type=int, default=3,
                       help="INFRA failures on one node before blacklisting")
    chaos.add_argument("--chaos-slow-task", default=None, metavar="TASK",
                       help="inject a straggler: slow this task's steps "
                            "(e.g. worker:1)")
    chaos.add_argument("--chaos-slow-step", type=int, default=0,
                       help="first slowed step (with --chaos-slow-task)")
    chaos.add_argument("--chaos-slow-until", type=int, default=None,
                       help="last slowed step (default: every step onward)")
    chaos.add_argument("--chaos-slow-delay", type=float, default=0.05,
                       help="extra seconds added to each slowed step")
    chaos.add_argument("--chaos-partition-src", default=None, metavar="TASK",
                       help="partition: one endpoint pattern (e.g. worker:0)")
    chaos.add_argument("--chaos-partition-dst", default="*", metavar="TASK",
                       help="partition: the other endpoint pattern")
    chaos.add_argument("--chaos-partition-step", type=int, default=None,
                       help="step-gated partition: first affected step "
                            "(raises from the src side)")
    chaos.add_argument("--chaos-partition-until", type=int, default=None,
                       help="step-gated partition: last affected step "
                            "(default: only --chaos-partition-step)")
    chaos.add_argument("--chaos-partition-after", type=float, default=0.0,
                       help="time-gated partition: seconds after task start")
    chaos.add_argument("--chaos-partition-duration", type=float, default=0.0,
                       help="time-gated partition: window length in seconds "
                            "(heartbeats dropped, rendezvous blocked)")
    spec = ap.add_argument_group(
        "speculation", "straggler detection + backups (core/speculation.py)")
    spec.add_argument("--speculation", action="store_true",
                      help="enable speculative execution for stragglers")
    spec.add_argument("--speculation-factor", type=float, default=2.0,
                      help="lagging iff progress * factor < gang median")
    spec.add_argument("--speculation-patience", type=int, default=5,
                      help="consecutive lagging observations before a backup")
    spec.add_argument("--speculation-min-progress", type=int, default=4,
                      help="gang median step before detection arms")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="tony-train-")

    plan = FaultPlan(seed=args.chaos_seed)
    if args.chaos_kill_step is not None:
        plan = plan.add(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  at_step=args.chaos_kill_step))
    if args.chaos_oom_step is not None:
        plan = plan.add(FaultSpec(FaultKind.OOM, task="worker:0",
                                  at_step=args.chaos_oom_step))
    if args.chaos_kill_ckpt_write is not None:
        plan = plan.add(FaultSpec(FaultKind.KILL_TASK, task="worker:0",
                                  at_step=args.chaos_kill_ckpt_write,
                                  in_ckpt_write=True))
    if args.chaos_random_faults:
        plan = FaultPlan(plan.seed, plan.faults + FaultPlan.random_plan(
            args.chaos_seed, steps=args.steps,
            n_faults=args.chaos_random_faults).faults)
    if args.chaos_slow_task:
        plan = plan.add(FaultSpec(FaultKind.SLOW_STEP, task=args.chaos_slow_task,
                                  at_step=args.chaos_slow_step,
                                  until_step=args.chaos_slow_until,
                                  delay_s=args.chaos_slow_delay))
    if args.chaos_partition_src:
        plan = plan.add(FaultSpec(FaultKind.PARTITION,
                                  src=args.chaos_partition_src,
                                  dst=args.chaos_partition_dst,
                                  at_step=args.chaos_partition_step,
                                  until_step=args.chaos_partition_until,
                                  after_s=args.chaos_partition_after,
                                  duration_s=args.chaos_partition_duration))

    events = EventLog()
    rm = make_cluster(num_gpu_nodes=4, num_cpu_nodes=2, gpus_per_node=4,
                      event_log=events,
                      chaos=FaultInjector(plan, events=events),
                      health=NodeHealthTracker(
                          threshold=args.blacklist_threshold, events=events))
    speculation = SpeculationPolicy(
        enabled=args.speculation,
        slowdown_factor=args.speculation_factor,
        patience=args.speculation_patience,
        min_progress=args.speculation_min_progress)
    client = TonYClient(YarnLikeBackend(rm, speculation=speculation))
    job = build_job(f"train-{cfg.name}", args.workers, args.ps,
                    min_workers=args.min_workers)

    steps_log = []
    prog = make_train_program(
        cfg, steps=args.steps, batch_size=args.batch_size, seq_len=args.seq_len,
        ckpt_dir=os.path.join(ckpt_dir, "ckpt"), ckpt_every=args.ckpt_every,
        strategy=args.strategy, lr=args.lr,
        on_step=lambda s, m: steps_log.append((s, m["loss"])))

    result = client.run_and_wait(job, prog)
    history = JobHistoryServer()
    history.record(job, result)
    summary = history.summary(result.app_id)
    print(json.dumps({
        "status": result.final_status,
        "attempts": len(result.attempts),
        "ui_url": result.ui_url,
        "first_loss": steps_log[0][1] if steps_log else None,
        "final_loss": steps_log[-1][1] if steps_log else None,
        "suggestions": [s.message for s in MetricsAnalyzer().analyze(job, result)],
        "failure_reasons": summary["failure_reasons"],
        "retry_advice": summary["retry_advice"],
        "resumed_attempts": summary["resumed_attempts"],
        "resized_attempts": summary["resized_attempts"],
        "blacklisted_nodes": summary["blacklisted_nodes"],
        "stragglers": summary["stragglers"],
        "speculation": summary["speculation"],
        "chaos_injected": events.count("chaos_injected"),
        "ckpt_committed": events.count("ckpt_committed"),
        "ckpt_dir": ckpt_dir,
    }, indent=2))
    if not result.succeeded:
        print(format_failure_report(result))
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
