"""Credit device programs and idle gaps to the program's host spans.

The program marks the steps of a search with ``repro.`` host spans
(``repro.obs``).  Device events in this runtime's traces carry no scope,
but each ``XLA Modules`` execution carries the ``run_id`` of the host
``DoEnqueueProgram`` that launched it.  A program is credited to every
``repro.`` span open when the host dispatched it, so the credit is
inclusive (``repro.engine.bounds`` holds its tiers).  The dispatch is the
enqueue itself, or, where the runtime deferred the enqueue to a worker
thread until the program's inputs were on the device, the
``tpu::System::Execute`` that the enqueue's
``tpu::System::Execute=>IssueSequencedEvent`` names by its flow id.

The window is ``trace_reduce``'s (the ``bench.request`` spans), or the
``repro.nn_search`` spans in a trace without them.  Busy time is the
union of ``XLA Ops`` intervals, as there; an op is attributed when the
program it ran in was credited to some span.  Idle gaps are labelled
``<bench span> / <innermost repro span> / <innermost host event>``; a
gap with no ``repro.`` span open keeps ``trace_reduce``'s label.

    python3 bench/span_reduce.py <trace dir or .xplane.pb[.gz]>

prints the reduction as one JSON object.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import heapq
import json
import os
import re
import sys

from jax.profiler import ProfileData

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace_reduce import (  # noqa: E402
    MODULES_LINE, OPS_LINE, WINDOW_SPAN, _clip, _length, _union,
    device_planes, host_events)

PREFIX = "repro."
CALL_SPAN = "repro.nn_search"
ENQUEUE = "DoEnqueueProgram"
SEQUENCED = "tpu::System::Execute=>IssueSequencedEvent"
DISPATCH = "tpu::System::Execute"
# host events of jaxpr tracing and lowering
LOWERING_RE = re.compile(r"^(lower_sharding_computation|trace_to_jaxpr\w*)$")


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    busy_s: float                     # averaged over the device planes
    span_host_s: dict[str, float]     # wall time of each span name
    span_device_s: dict[str, float]   # device time credited, inclusive
    attributed_frac: float            # busy time credited to some span
    lowering_host_s: dict[str, float]  # lowering host events by span
    idle_gaps: list[tuple[str, float]]
    n_devices: int

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["idle_gaps"] = [[n, s] for n, s in self.idle_gaps]
        return d


def _open_at(spans, times) -> list[list[str]]:
    """For each time, the ``(name, start, end)`` spans open at it, from
    the outermost to the innermost."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    order = sorted(range(len(times)), key=lambda k: times[k])
    out = [[] for _ in times]
    active, nxt = [], 0
    for k in order:
        t = times[k]
        while nxt < len(spans) and spans[nxt][1] <= t:
            active.append(spans[nxt])
            nxt += 1
        active = [a for a in active if a[2] > t]
        out[k] = [a[0] for a in active]
    return out


def labels(hosts, times) -> list[str]:
    """``trace_reduce._host_labels`` with the innermost ``repro.`` span
    between the bench span and the innermost host event."""
    events = sorted(hosts, key=lambda h: h[1])
    order = sorted(range(len(times)), key=lambda k: times[k])
    out = [""] * len(times)
    inner, bench, repro = [], [], []
    nxt = 0
    for k in order:
        t = times[k]
        while nxt < len(events) and events[nxt][1] <= t:
            n, s, e = events[nxt]
            heapq.heappush(inner, (-s, e, n))
            if n.startswith("bench."):
                heapq.heappush(bench, (-s, e, n))
            if n.startswith(PREFIX):
                heapq.heappush(repro, (-s, e, n))
            nxt += 1
        for heap in (inner, bench, repro):
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
        name = inner[0][2] if inner else "<no host event>"
        parts = [name]
        if repro and repro[0][2] != name:
            parts.insert(0, repro[0][2])
        if bench and bench[0][2] != parts[0]:
            parts.insert(0, bench[0][2])
        out[k] = " / ".join(parts)
    return out


def attribute(hosts, dispatch, planes, lo, hi, top: int = 10
              ) -> SpanSummary:
    """The reduction over plain events.

    ``hosts``: ``(name, start, end)`` host events; ``dispatch``:
    ``{run_id: time}`` of each program's dispatch; ``planes``: per
    device, ``(modules, ops)`` with ``modules`` the ``(start, end,
    run_id)`` of each program execution and ``ops`` the ``(start,
    end)`` of each op; the window is ``[lo, hi)``."""
    spans = [h for h in hosts if h[0].startswith(PREFIX)]
    host_s = collections.Counter()
    for n, s, e in _clip_named(spans, lo, hi):
        host_s[n] += (e - s) * 1e-9
    runs = list(dispatch)
    credited = dict(zip(runs, (set(o) for o in _open_at(
        spans, [dispatch[r] for r in runs]))))
    device_s = collections.Counter()
    busy_total = attributed = 0.0
    gaps = []
    for modules, ops in planes:
        mods = sorted(modules)
        starts = [m[0] for m in mods]
        for s, e, rid in mods:
            if e > lo and s < hi:
                for name in credited.get(rid, ()):
                    device_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
        ops = _clip(ops, lo, hi)
        busy = _union(ops)
        busy_total += _length(busy) * 1e-9
        mine = []
        for s, e in ops:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mods[k][1] and credited.get(mods[k][2]):
                mine.append((s, e))
        attributed += _length(_union(mine)) * 1e-9
        prev = lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    named = collections.Counter()
    for (s, e), label in zip(gaps, labels(hosts, [(s + e) // 2
                                                  for s, e in gaps])):
        named[label] += (e - s) * 1e-9
    low = [h for h in _clip_named(hosts, lo, hi) if LOWERING_RE.match(h[0])]
    by_span = collections.defaultdict(list)
    for (n, s, e), open_ in zip(low, _open_at(spans, [h[1] for h in low])):
        if open_:
            by_span[open_[-1]].append((s, e))
    n = max(len(planes), 1)
    return SpanSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n,
        span_host_s=dict(host_s),
        span_device_s={k: v / n for k, v in device_s.items()},
        attributed_frac=attributed / busy_total if busy_total else 0.0,
        lowering_host_s={k: _length(_union(iv)) * 1e-9
                         for k, iv in by_span.items()},
        idle_gaps=[(k, v / n) for k, v in named.most_common(top)],
        n_devices=len(planes),
    )


def _clip_named(events, lo, hi):
    """``(name, start, end)`` events clipped to ``[lo, hi)``."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _dispatch_times(profile) -> dict:
    """``{run_id: time}``: each program's enqueue, or the dispatch that a
    deferred enqueue's flow id names."""
    execs = {}
    enqueues = []
    for p in profile.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            seq = None        # (end, flow id) of the open SEQUENCED event
            for e in sorted(line.events, key=lambda x: x.start_ns):
                if e.name == DISPATCH:
                    flow = dict(e.stats).get("_p")
                    if flow is not None:
                        execs[flow] = e.start_ns
                elif e.name == SEQUENCED:
                    seq = (e.start_ns + e.duration_ns,
                             dict(e.stats).get("_c"))
                elif e.name == ENQUEUE:
                    rid = dict(e.stats).get("run_id")
                    flow = seq[1] if seq and e.start_ns < seq[0] \
                        else None
                    if rid is not None:
                        enqueues.append((rid, e.start_ns, flow))
    return {rid: execs.get(flow, t) for rid, t, flow in enqueues}


def _planes(profile):
    out = []
    for p in device_planes(profile):
        modules, ops = [], []
        for line in p.lines:
            if line.name == MODULES_LINE:
                for e in line.events:
                    rid = dict(e.stats).get("run_id")
                    modules.append((e.start_ns, e.start_ns + e.duration_ns,
                                    rid))
            elif line.name == OPS_LINE:
                ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
        out.append((modules, ops))
    return out


def reduce(profile, span: str = WINDOW_SPAN, top: int = 10) -> SpanSummary:
    hosts = host_events(profile)
    marks = [(s, e) for n, s, e in hosts if n == span] or \
        [(s, e) for n, s, e in hosts if n == CALL_SPAN]
    if not marks:
        raise ValueError(f"no host span {span!r} or {CALL_SPAN!r} in the "
                         "trace")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    return attribute(hosts, _dispatch_times(profile), _planes(profile),
                     lo, hi, top)


def load(path: str) -> ProfileData:
    """A trace from a ``.xplane.pb``, a gzipped one, or the directory a
    ``jax.profiler`` trace wrote (which must hold one)."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"expected one .xplane.pb under {path}, "
                             f"found {len(found)}")
        path = found[0]
    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(reduce(load(args[0])).to_dict()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
