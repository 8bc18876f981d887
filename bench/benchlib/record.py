"""What one run saw, on the host's monotonic clock, for the metric readers.

Every time here is ``time.monotonic()`` of this process: the TonY event log,
the step clock (the program's ``on_step``, called once the step's loss has
reached the host, so the device has finished the step), the chief program's
entry (taken by the bench's wrapper around the program) and JAX's compile
events all use it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Step:
    t: float
    attempt: int
    step: int
    loss: float
    grad_norm: float


@dataclass
class RunRecord:
    cell: object                       # spec.Cell
    seconds: float
    setup_s: float
    t_submit: float
    t_open: float                      # the first completed step
    t_close: float                     # t_open + seconds
    steps: list[Step]
    entries: dict[int, float]          # attempt -> chief program entry
    chaos: list[float]                 # chaos_injected times
    commits: list[dict]                # ckpt_committed payloads, with "t"
    compiles: list[tuple[float, float, str, bool]]  # end, secs, name, hit
    flops_per_step: float
    tokens_per_step: int
    peak_flops: float
    chips: int
    trace: dict | None = None          # trace.reduce() of the traced window

    # -- the step clock ---------------------------------------------------
    def first_step(self, attempt: int) -> float | None:
        ts = [s.t for s in self.steps if s.attempt == attempt]
        return min(ts) if ts else None

    def progress(self, t: float) -> float:
        """Net training progress at time t: the highest step completed,
        counted in steps, interpolated across a step in flight. Steps
        recomputed after a resume do not raise it until they pass the old
        mark, and nothing is interpolated across a kill and resume."""
        mark, t_mark, attempt = -1, None, None
        for s in sorted(self.steps, key=lambda s: s.t):
            if s.t > t:
                if (t_mark is not None and s.step == mark + 1
                        and s.attempt == attempt):
                    return mark + (t - t_mark) / (s.t - t_mark)
                return float(mark)
            if s.step > mark:
                mark, t_mark, attempt = s.step, s.t, s.attempt
        return float(mark)

    def net_steps(self) -> float:
        return self.progress(self.t_close) - self.progress(self.t_open)

    def intervals(self) -> list[tuple[int, float]]:
        """(step, seconds) between consecutive completed steps of one
        attempt, both inside the window; ``step`` is the later one."""
        out = []
        by_attempt: dict[int, list[Step]] = {}
        for s in self.steps:
            if self.t_open <= s.t <= self.t_close:
                by_attempt.setdefault(s.attempt, []).append(s)
        for steps in by_attempt.values():
            steps.sort(key=lambda s: s.t)
            for a, b in zip(steps, steps[1:]):
                if b.step == a.step + 1:
                    out.append((b.step, b.t - a.t))
        return out

    def window_steps(self) -> list[Step]:
        return [s for s in self.steps if self.t_open <= s.t <= self.t_close]
