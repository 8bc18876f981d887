"""Drive one TonY training job and record what the window needs.

The job goes through the system's own entry: ``TonYClient.submit`` -> RM ->
AM -> executors -> the chief's ``make_train_program`` loop -> the jitted step
of ``distributed/steps.make_train_fn``. The bench adds three things around
it, none of which changes what the job computes:

* a wrapper around the ML program that notes when the chief's program is
  entered in each attempt, and keeps the attempt's ``JobContext`` so that
  the bench can stop the job once it is measured;
* the program's ``on_step`` callback, the step clock;
* a wrapper around the jitted step that the program builds, which reads
  the state at the steps the correctness check compares (the weights before
  step 0, the first moment after step 0, the weights after the last
  compared step) and annotates each dispatch in the profiler's trace.

The job runs an open-ended number of steps and writes no save it does not
plan: it is stopped through its context's cancel event once the window has
closed (and, in a kill cell, once the relaunched attempt has stepped).
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp

from benchlib.record import Step

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Every backend compile in this process, with whether the persistent
    cache served it. A compile that hits the cache still reports its (short)
    duration. Listeners cannot be removed, so make one per process."""

    def __init__(self):
        self.compiles: list[tuple[float, float, str, bool]] = []
        self._hit = False
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles.append((time.monotonic(), secs,
                                      str(kw.get("fun_name")), self._hit))
                self._hit = False


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class StateReader:
    """The wrapper around the program's jitted step (see the module doc).

    Its programs are small reductions; set-up runs each once (``warm``) so
    that none compiles inside the window. Results stay on the device until
    the job has ended. ``fault`` breaks the step underneath, for the tests
    that show a broken step fails the check: "unchanged" hands back the
    state it was given, "half_batch" drops half the batch's rows."""

    def __init__(self, compared_steps: int, fault: str | None = None):
        self.compared_steps = compared_steps
        self.fault = fault
        self.copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self.norms = jax.jit(_leaf_norms)
        self.diff_norms = jax.jit(_diff_norms)
        self.names: list[str] | None = None
        self.moment0 = None            # |m| per leaf after step 0
        self.change = None             # |w_k - w_0| per leaf, k compared
        self._w0 = None

    def warm(self, state) -> None:
        w0 = self.copy(state["params"])
        jax.block_until_ready((self.norms(state["opt"]["m"]),
                               self.diff_norms(state["params"], w0)))

    def wrap(self, train_fn, attempt: int):
        calls = iter(range(1 << 62))

        def step(state, batch):
            i = next(calls)
            first = attempt == 1 and i == 0
            with jax.profiler.TraceAnnotation("bench.dispatch_step"):
                if first:
                    self.names = leaf_names(state["params"])
                    self._w0 = self.copy(state["params"])
                if self.fault == "half_batch":
                    half = batch["tokens"].shape[0] // 2
                    batch = {k: v[:half] for k, v in batch.items()}
                kept = self.copy(state) if self.fault == "unchanged" else None
                state, metrics = train_fn(state, batch)
                if kept is not None:
                    state = kept
                if first:
                    self.moment0 = self.norms(state["opt"]["m"])
                if attempt == 1 and i == self.compared_steps - 1:
                    self.change = self.diff_norms(state["params"], self._w0)
                    self._w0 = None
            return state, metrics

        return step


class Recorder:
    """The chief's program entries, its steps, and the stop switch."""

    def __init__(self):
        self.lock = threading.Condition()
        self.steps: list[Step] = []
        self.entries: dict[int, float] = {}
        self.attempt = 0
        self.ctx = None
        self.stopped = False

    def wrap_program(self, inner):
        def program(env, ctx):
            if env["TASK_TYPE"] == "worker" and env["TASK_INDEX"] == "0":
                with self.lock:
                    self.attempt = int(ctx.shared.get("attempt", 1))
                    self.entries[self.attempt] = time.monotonic()
                    self.ctx = ctx
                    if self.stopped:
                        ctx.cancel.set()
            return inner(env, ctx)
        return program

    def on_step(self, step: int, metrics: dict) -> None:
        with self.lock:
            self.steps.append(Step(time.monotonic(), self.attempt, step,
                                   float(metrics["loss"]),
                                   float(metrics["grad_norm"])))
            self.lock.notify_all()

    def wait_for(self, pred, timeout: float) -> bool:
        with self.lock:
            return self.lock.wait_for(pred, timeout)

    def stop(self) -> None:
        with self.lock:
            self.stopped = True
            if self.ctx is not None:
                self.ctx.cancel.set()

    def first_step_of(self, attempt: int) -> float | None:
        ts = [s.t for s in self.steps if s.attempt == attempt]
        return min(ts) if ts else None
