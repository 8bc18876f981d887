"""The program's own spans (``repro.core.tracing``), cut to one run.

Span times are ``time.monotonic()`` of this process, the clock of the run's
record. A program without spans gives None, so that its readers report
nothing there and raise nothing.
"""
from __future__ import annotations


def run_spans(run) -> list | None:
    """Every span that started after this run's submit, oldest first."""
    try:
        from repro.core.tracing import spans
    except ImportError:
        return None
    return spans(since=run.t_submit)


def in_window(run, spans: list, name: str) -> list:
    """The spans named ``name`` that lie wholly inside the run's window."""
    return [s for s in spans if s.name == name
            and run.t_open <= s.start and s.end <= run.t_close]


def children(spans: list) -> dict:
    """span id -> {child name: [child spans]}."""
    out: dict = {}
    for s in spans:
        if s.parent_id is not None:
            out.setdefault(s.parent_id, {}).setdefault(s.name, []).append(s)
    return out


def of_attempt(run, spans: list, name: str, attempt: int):
    """The first span named ``name`` of the chief's attempt ``attempt``,
    at or after that attempt's program entry, or None."""
    entry = run.entries.get(attempt)
    if entry is None:
        return None
    return next((s for s in spans if s.name == name and s.start >= entry
                 and s.attrs.get("attempt") == attempt), None)
