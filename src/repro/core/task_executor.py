"""TaskExecutor — runs inside each allocated container.

Lifecycle (paper §2.2, step-for-step):
  1. allocate a port, register (host, port) with the AM
  2. wait for the AM's global cluster spec broadcast
  3. materialize the spec + task config as environment variables
  4. spawn the ML program as a child "process" (a callable in a thread)
  5. heartbeat to the AM while the child runs; the first worker also
     registers a visualization UI port (TensorBoard analogue)
  6. register the final exit status with the AM and terminate
"""
from __future__ import annotations

import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable

from repro.core.chaos import NO_CHAOS, FaultInjector
from repro.core.cluster_spec import TaskAddress, task_env
from repro.core.events import EventLog
from repro.core.failures import (
    EXIT_EXECUTOR_ERROR,
    EXIT_SPECULATION_LOST,
    FailureClass,
    TaskDiagnostics,
    diagnose_exception,
)
from repro.core.resources import Container, PortAllocator
from repro.core.speculation import speculative_id

# MLProgram: (env, job_context) -> exit code
MLProgram = Callable[[dict[str, str], "JobContext"], int]


class CancellableBarrier:
    """Reusable barrier that unblocks (returning False) on cancel/timeout
    instead of breaking permanently like threading.Barrier."""

    def __init__(self, n: int):
        self.n = n
        self._count = 0
        self._generation = 0
        self._cond = threading.Condition()

    def wait(self, cancel: threading.Event | None = None,
             timeout: float = 300.0) -> bool:
        deadline = time.monotonic() + timeout
        with self._cond:
            gen = self._generation
            self._count += 1
            if self._count == self.n:
                self._count = 0
                self._generation += 1
                self._cond.notify_all()
                return True
            while self._generation == gen:
                if (cancel is not None and cancel.is_set()) or \
                        time.monotonic() > deadline:
                    self._count -= 1
                    return False
                self._cond.wait(0.05)
            return True

    def reduce(self, n: int = 1) -> None:
        """Shrink the party by ``n`` (elastic gang resize: a shed member will
        never arrive). Releases current waiters if they now form a full
        party."""
        with self._cond:
            self.n = max(1, self.n - n)
            if self._count >= self.n:
                self._count = 0
                self._generation += 1
                self._cond.notify_all()


@dataclass
class JobContext:
    """In-process stand-in for the ML framework's own distributed transport.

    TonY is framework-agnostic: after launch, tasks coordinate via the
    framework's protocol (RPC/MPI/...). In this single-process simulation the
    context carries a barrier + shared dict so all task childs of one job
    attempt can rendezvous — mirroring the launch-time contract without
    reimplementing NCCL.
    """
    world_size: int
    barrier: CancellableBarrier = None  # type: ignore[assignment]
    shared: dict[str, Any] = field(default_factory=dict)
    cancel: threading.Event = field(default_factory=threading.Event)
    workdir: str = ""
    # fault-injection hooks for the ML program (``ctx.chaos.check_step``);
    # NO_CHAOS by default so programs can call it unconditionally
    chaos: FaultInjector = None  # type: ignore[assignment]
    # per-executor step progress (exec_id -> latest step), written by the ML
    # program via ``report_progress``/``step`` and read by the executor's
    # heartbeat loop — the AM's straggler detection feeds off it
    progress: dict[str, int] = field(default_factory=dict)
    # event log for ML-program-side telemetry (e.g. ckpt_committed); None in
    # bare unit contexts
    events: EventLog | None = None
    # flush callbacks for in-flight async work (checkpoint writer,
    # prefetcher): registered by the ML program, drained by graceful
    # teardown paths so no committed-but-unpublished work is lost
    _flushers: list[Callable[[], None]] = field(default_factory=list)

    def __post_init__(self):
        if self.barrier is None:
            self.barrier = CancellableBarrier(self.world_size)
        if self.chaos is None:
            self.chaos = NO_CHAOS

    def rendezvous(self, timeout: float = 300.0,
                   exec_id: str | None = None, attempt: int = 0) -> bool:
        """Gang barrier. When the caller identifies itself (``exec_id``),
        an open chaos PARTITION window blocks it *before* it joins the
        barrier — a partitioned task can't reach its peers — until the
        window closes, cancel fires, or the timeout burns down."""
        deadline = time.monotonic() + timeout
        while self.chaos.partition_active(exec_id, attempt):
            if self.cancel.is_set() or time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        remaining = max(0.0, deadline - time.monotonic())
        return self.barrier.wait(self.cancel, remaining)

    def shrink_world(self, n: int = 1) -> None:
        """Elastic resize mid-attempt: an INFRA-lost member above the floor
        was shed, so future barriers expect one fewer participant. Pending
        async work is flushed first — a resize must not strand a checkpoint
        that already finished staging."""
        self.flush_async()
        self.world_size = max(1, self.world_size - n)
        self.shared["world_size"] = self.world_size
        self.barrier.reduce(n)

    def register_flusher(self, fn: Callable[[], None]) -> None:
        """Register a flush hook for in-flight async work (async checkpoint
        writer, prefetch loader). Graceful teardown paths call
        ``flush_async`` so committed work is published before exit."""
        self._flushers.append(fn)

    def flush_async(self) -> None:
        """Drain registered flushers. Never raises: a flusher's deferred
        error belongs to the thread that owns it (the ML program re-raises
        it from its own save/flush), not to teardown."""
        for fn in list(self._flushers):
            try:
                fn()
            except Exception:  # noqa: BLE001 - teardown must proceed
                pass

    def report_progress(self, exec_id: str, step: int) -> None:
        self.progress[exec_id] = step

    def step(self, exec_id: str, attempt: int, step: int) -> None:
        """One training step's orchestrator side: record progress (carried to
        the AM by the next executor heartbeat, driving straggler detection)
        and consult the chaos plan (which may delay the step — SLOW_STEP —
        or raise a planned fault)."""
        self.progress[exec_id] = step
        self.chaos.check_step(exec_id, attempt, step)


class TaskExecutor:
    HEARTBEAT_INTERVAL_S = 0.02
    # how long a finished task drains its async work, heartbeating, before
    # it reports a hung flusher as its failure (the checkpoint writer's own
    # close timeout)
    DRAIN_TIMEOUT_S = 30.0

    def __init__(self, task_type: str, index: int, container: Container,
                 am: "ApplicationMasterProtocol", ml_program: MLProgram,
                 job_args: dict[str, str], ctx: JobContext,
                 ports: PortAllocator, events: EventLog,
                 is_chief_worker: bool = False,
                 chaos: FaultInjector | None = None,
                 speculative: bool = False):
        self.task_type = task_type
        self.index = index
        self.container = container
        self.am = am
        self.ml_program = ml_program
        self.job_args = job_args
        self.ctx = ctx
        self.ports = ports
        self.events = events
        self.is_chief_worker = is_chief_worker
        self.chaos = chaos or ctx.chaos or NO_CHAOS
        self.task_id = f"{task_type}:{index}"
        # a speculative backup copy runs the same (task_type, index) under a
        # copy-suffixed id so its heartbeats/exits/logs/chaos hooks stay
        # distinct from the original's; it skips registration (the gang's
        # cluster spec is already built) — the AM pre-delivers the spec
        self.speculative = speculative
        self.exec_id = speculative_id(self.task_id) if speculative else self.task_id
        # per-executor teardown, distinct from ctx.cancel (whole-gang): the
        # AM sets this to kill one copy after a speculation race resolves
        self.cancel = threading.Event()
        self.exit_status: int | None = None
        self.diagnostics: TaskDiagnostics | None = None
        self.log_lines: list[str] = []
        self.metrics: dict[str, float] = {}
        self._cluster_spec_ready = threading.Event()
        self._cluster_spec: dict | None = None
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------------
    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name=f"executor-{self.exec_id}",
                                        daemon=True)
        self._thread.start()

    def join(self, timeout: float | None = None) -> None:
        if self._thread:
            self._thread.join(timeout)

    def deliver_cluster_spec(self, spec: dict) -> None:
        self._cluster_spec = spec
        self._cluster_spec_ready.set()

    def log(self, line: str) -> None:
        self.log_lines.append(line)

    # ------------------------------------------------------------------
    def _run(self) -> None:
        src = f"executor:{self.exec_id}"
        try:
            # 1. port allocation + registration (speculative copies skip
            # registration: the gang already rendezvoused and the cluster
            # spec is pre-delivered by the AM before start())
            port = self.ports.allocate()
            addr = TaskAddress(self.task_type, self.index,
                               self.container.node_id, port)
            ui_port = None
            if not self.speculative:
                if self.is_chief_worker:
                    ui_port = self.ports.allocate()  # TensorBoard analogue
                self.events.emit(src, "task_registering", endpoint=addr.endpoint)
                self.am.register_task(self, addr, ui_port=ui_port)

            # 2. wait for the global cluster spec
            if not self._cluster_spec_ready.wait(timeout=60.0):
                raise TimeoutError("cluster spec broadcast never arrived")

            # 3. env materialization
            env = task_env(self._cluster_spec, self.task_type, self.index,
                           self.job_args)
            env["CONTAINER_ID"] = self.container.container_id
            env["UI_PORT"] = str(ui_port) if ui_port else ""
            if self.speculative:
                env["SPECULATIVE"] = "1"
            self.events.emit(src, "task_env_ready", world=env["WORLD_SIZE"])

            # 4. spawn the child + 5. heartbeat until done
            result: dict[str, Any] = {}

            def child():
                try:
                    result["exit"] = int(self.ml_program(env, self.ctx) or 0)
                except Exception as e:  # noqa: BLE001 - child crash is data
                    self.log(f"child crashed: {type(e).__name__}: {e}")
                    self.log(traceback.format_exc())
                    result["exit"] = 1
                    # capture the failure for the AM: type, message and the
                    # full formatted traceback, pre-classified
                    diag = diagnose_exception(self.exec_id, e)
                    result["diag"] = diag
                    self.ctx.shared[f"diag:{self.exec_id}"] = diag.to_dict()

            child_t = threading.Thread(target=child, name=f"ml-{self.exec_id}",
                                       daemon=True)
            child_t.start()
            attempt = int(self.ctx.shared.get("attempt", 1))
            self.chaos.task_started(self.exec_id, attempt)

            def beat() -> None:
                if self.chaos.drop_heartbeat(self.exec_id, attempt) or \
                        self.chaos.partition_active(self.exec_id, attempt):
                    # chaos: simulated network partition — the AM sees a
                    # silent task and attributes a heartbeat timeout
                    return
                # heartbeats carry the child's latest step so the AM can
                # spot stragglers (core/speculation.py)
                self.am.heartbeat(self.exec_id,
                                  progress=self.ctx.progress.get(self.exec_id))

            while child_t.is_alive():
                beat()
                if self.ctx.cancel.is_set():
                    # AM-initiated teardown: abandon the child (thread stand-in
                    # for SIGKILL on the real container process)
                    self.log("teardown requested; abandoning child")
                    result.setdefault("exit", 143)
                    break
                if self.cancel.is_set():
                    # this copy lost its speculation race — benign teardown,
                    # classified TRANSIENT and never charged to the node
                    self.log("lost the speculation race; torn down")
                    result.setdefault("exit", EXIT_SPECULATION_LOST)
                    break
                if self.container.state.value == "preempted" or \
                        self.chaos.should_preempt(self.exec_id, attempt):
                    # the scheduler reclaimed this container (capacity-
                    # scheduler preemption, organic or chaos-injected);
                    # report SIGKILL-style exit so the AM relaunches via the
                    # normal fault-tolerance path
                    self.log("container preempted by scheduler")
                    result.setdefault("exit", 137)
                    break
                child_t.join(self.HEARTBEAT_INTERVAL_S)

            # graceful teardown: let in-flight async work (checkpoint
            # writer, prefetcher) finish committing before the exit is
            # reported — an already-staged checkpoint must still publish
            # its ckpt_step so the next attempt resumes from it. The task
            # keeps heartbeating while it drains: a write of a large state
            # outlasts the AM's heartbeat timeout, and a silent drain would
            # turn this exit into a lost-heartbeat failure
            drain = threading.Thread(target=self.ctx.flush_async,
                                     name=f"drain-{self.exec_id}", daemon=True)
            drain.start()
            deadline = time.monotonic() + self.DRAIN_TIMEOUT_S
            while drain.is_alive() and time.monotonic() < deadline:
                beat()
                drain.join(self.HEARTBEAT_INTERVAL_S)
            self.exit_status = int(result.get("exit", 0))
            self.diagnostics = result.get("diag")
            if drain.is_alive():
                # a hung writer must not keep a finished task alive: the
                # task fails, and says why unless the child already did
                self.log(f"async work did not drain in {self.DRAIN_TIMEOUT_S} s")
                self.exit_status = self.exit_status or EXIT_EXECUTOR_ERROR
                self.diagnostics = self.diagnostics or TaskDiagnostics(
                    task_id=self.exec_id, exit_status=self.exit_status,
                    classification=FailureClass.INFRA,
                    exception_type="DrainTimeout",
                    message="in-flight async work (checkpoint writer, "
                            "prefetcher) did not finish within "
                            f"{self.DRAIN_TIMEOUT_S} s of the child's exit")
            self.metrics = dict(self.ctx.shared.get(f"metrics:{self.exec_id}", {}))
        except Exception as e:  # noqa: BLE001
            self.log(f"executor error: {e}")
            self.exit_status = EXIT_EXECUTOR_ERROR
            self.diagnostics = TaskDiagnostics(
                task_id=self.exec_id, exit_status=EXIT_EXECUTOR_ERROR,
                classification=FailureClass.INFRA,
                exception_type=type(e).__name__, message=str(e),
                traceback=traceback.format_exc())
        finally:
            self.events.emit(src, "task_finished", exit=self.exit_status)
            self.am.report_exit(self.exec_id, self.exit_status or 0,
                                diagnostics=self.diagnostics)


class ApplicationMasterProtocol:
    """Interface TaskExecutors call back into (implemented by the AM)."""

    def register_task(self, executor: TaskExecutor, addr: TaskAddress,
                      ui_port: int | None = None) -> None:
        raise NotImplementedError

    def heartbeat(self, task_id: str, progress: int | None = None) -> None:
        raise NotImplementedError

    def report_exit(self, task_id: str, status: int,
                    diagnostics: TaskDiagnostics | None = None) -> None:
        raise NotImplementedError


def _now() -> float:
    return time.monotonic()
