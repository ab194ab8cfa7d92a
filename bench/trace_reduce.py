"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

Read with ``jax.profiler.ProfileData``.  The window is the span from the
first host ``bench.request`` annotation's start to the last one's end.
On each device plane the operations are the events of its ``XLA Ops``
line, each named by its HLO instruction; busy time is the union of
their intervals inside the window (a ``while`` op spans its body's ops,
so its loop control counts as busy).  A Pallas kernel is an op whose
instruction bears its jitted wrapper's name (``%dtw_band_pallas.3 =
... custom-call(...)``).  The breakdown's device ops are labelled
``<program>:<instruction>`` (the program from the ``XLA Modules`` line)
with self time, nested ops taken off their parent.  Idle gaps are named
by the innermost host event open across the gap's middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import heapq
import os
import re

from jax.profiler import ProfileData

WINDOW_SPAN = "bench.request"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an op event is named by its HLO instruction, "%<name>.<n> = <shape> ...";
# a Pallas kernel's custom call takes its jitted wrapper's name
KERNEL_RE = re.compile(r"^\w+_pallas$")
MODULE_RE = re.compile(r"^([^(]+)")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the device planes
    kernel_s: dict[str, float]         # per *_pallas kernel, device time
    other_s: float                     # busy time in no kernel event
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]
    n_devices: int

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def _union(intervals):
    """Merge ``(start, end)`` intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """The HLO instruction's name without its numeric suffix
    (``%fusion.125 = f32[...] fusion(...)`` -> ``fusion``)."""
    return event_name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]


def kernel_name(event_name: str) -> str | None:
    """The ``*_pallas`` kernel an op event runs, if it is one."""
    name = op_name(event_name)
    return name if KERNEL_RE.match(name) else None


def _module_of(modules, t) -> str:
    """The name of the program (``XLA Modules`` event) running at ``t``."""
    k = bisect.bisect_right(modules[0], t) - 1
    if k >= 0 and t < modules[1][k]:
        return modules[2][k]
    return "?"


def _self_times(events):
    """Duration of each ``(start, end, label)`` minus the events nested
    directly inside it (a ``while`` op holds its body's ops)."""
    out = collections.Counter()
    stack = []   # [end, label, self]
    for s, e, label in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, lab, own = stack.pop()
            out[lab] += own
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, label, e - s])
    for end, lab, own in stack:
        out[lab] += own
    return out


def device_planes(profile):
    return [p for p in profile.planes if p.name.startswith("/device:")
            and any(line.name == OPS_LINE for line in p.lines)]


def host_events(profile):
    """``(name, start_ns, end_ns)`` of every host event with a duration."""
    out = []
    for p in profile.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def reduce(profile, span: str = WINDOW_SPAN, top: int = 10) -> Summary:
    hosts = host_events(profile)
    marks = [(s, e) for n, s, e in hosts if n == span]
    if not marks:
        raise ValueError(f"no host span {span!r} in the trace")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    planes = device_planes(profile)
    if not planes:
        raise ValueError("no device plane with an 'XLA Ops' line")
    busy_total = 0.0
    other_total = 0.0
    kernels = collections.Counter()
    by_op = collections.Counter()
    gaps = []
    for p in planes:
        ops, kern, labelled = [], [], []
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       MODULE_RE.match(e.name).group(1).strip())
                      for line in p.lines if line.name == MODULES_LINE
                      for e in line.events)
        modules = ([m[0] for m in mods], [m[1] for m in mods],
                   [m[2] for m in mods])
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                (s, t), = _clip([(s, t)], lo, hi)
                ops.append((s, t))
                k = kernel_name(e.name)
                if k:
                    kern.append((s, t))
                    kernels[k] += (t - s) * 1e-9
                labelled.append((s, t, f"{_module_of(modules, s)}:"
                                       f"{op_name(e.name)}"))
        for label, own in _self_times(labelled).items():
            by_op[label] += own * 1e-9
        busy = _union(ops)
        busy_total += _length(busy) * 1e-9
        other_total += (_length(busy) - _length(_union(kern))) * 1e-9
        prev = lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    named = collections.Counter()
    for (s, e), label in zip(gaps, _host_labels(hosts, [(s + e) // 2
                                                        for s, e in gaps])):
        named[label] += (e - s) * 1e-9
    n = len(planes)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n,
        kernel_s={k: v / n for k, v in kernels.items()},
        other_s=other_total / n,
        device_ops=[(k, v / n) for k, v in by_op.most_common(top)],
        idle_gaps=[(k, v / n) for k, v in named.most_common(top)],
        n_devices=n,
    )


def _host_labels(hosts, times) -> list[str]:
    """For each time, ``<innermost bench span> / <innermost host event>``
    open at it (the open event that started last is the innermost)."""
    events = sorted(hosts, key=lambda h: h[1])
    order = sorted(range(len(times)), key=lambda k: times[k])
    labels = [""] * len(times)
    inner, bench = [], []
    nxt = 0
    for k in order:
        t = times[k]
        while nxt < len(events) and events[nxt][1] <= t:
            n, s, e = events[nxt]
            heapq.heappush(inner, (-s, e, n))
            if n.startswith("bench."):
                heapq.heappush(bench, (-s, e, n))
            nxt += 1
        for heap in (inner, bench):
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
        name = inner[0][2] if inner else "<no host event>"
        if bench and bench[0][2] != name:
            name = f"{bench[0][2]} / {name}"
        labels[k] = name
    return labels


def reduce_file(path: str, **kw) -> Summary:
    return reduce(ProfileData.from_file(path), **kw)


def reduce_dir(trace_dir: str, **kw) -> Summary:
    """Reduce the one ``.xplane.pb`` that a trace wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return reduce_file(found[0], **kw)
