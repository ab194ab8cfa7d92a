"""What ``repro.obs`` counted over a traced window, for metric readers.

The readers run after the window, in the process that ran it.
``repro.obs`` keeps the counters of the ``nn_search`` calls made since
the last profiler trace began; in a ``--trace 1`` run the trace holds
the window alone, so these are the window's requests.  A checkout whose
program has no ``repro.obs`` reads nothing."""

from __future__ import annotations


def traced(run) -> dict | None:
    """``repro.obs.snapshot()["trace"]`` for a traced run, else ``None``."""
    if run.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()["trace"]


def per_request(run, key: str, scale: float = 1.0) -> float | None:
    """A traced counter over the window's requests."""
    counts = traced(run)
    if counts is None:
        return None
    return counts[key] * scale / len(run.requests)


def span_ms_per_request(run, span: str) -> float | None:
    """Wall time of a ``repro.`` span over the window's requests."""
    counts = traced(run)
    if counts is None or span not in counts["span_s"]:
        return None
    return counts["span_s"][span] * 1e3 / len(run.requests)
