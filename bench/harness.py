"""One run of one benchmark cell: set-up, a timed window, the check.

Everything that belongs to one deployment, traffic mix, cell or metric
is a file of its own, found by name (``Catalog``):

  configs/<config>.json   the deployment: store and serve kinds, sizes,
                          window, engine settings, source and cuts
  traffic/<mix>.json      the query and loop kinds, batch, pool, and how
                          many answers the check compares
  limits/<cell>.json      the limit of each number the check compares
  metrics/<metric>.py     ``read(run) -> float | None`` for one metric

and the code of each kind is a module of its own, found by the name the
data files give:

  stores/<store>.py       ``make(cfg) -> generators.Data``
  serve/<serve>.py        ``make(cfg, data) -> serve(q) -> record.Answer``
                          (builds the index; the system under test)
  queries/<queries>.py    ``make(mix, cfg, data, seed) -> source`` with
                          ``source.batch(i)``
  loops/<loop>.py         ``run(serve, source, seconds, batch, loud)
                          -> (requests, loud warnings)``

So a later cell with another store, search path or arrival process adds
files and entries and edits none.  Nothing here picks a device:
``run.py`` refuses to start without a TPU, and the CPU tests call these
functions directly at small sizes.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
import warnings
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import check as _check
from bench import generators as gen
from bench.record import Request, Run

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


class Catalog:
    """Finds a deployment, traffic mix, cell limits or metric reader by
    its name: the first of ``roots`` that has the file wins (the
    benchmark's own directory by default)."""

    def __init__(self, *roots: Path):
        self.roots = [Path(r) for r in roots] or [BENCH]

    def _find(self, kind: str, name: str, suffix: str) -> Path:
        for root in self.roots:
            path = root / kind / f"{name}{suffix}"
            if path.is_file():
                return path
        raise KeyError(f"no {kind} file named {name}{suffix} under "
                       f"{', '.join(map(str, self.roots))}")

    def _json(self, kind: str, name: str) -> dict:
        return json.loads(self._find(kind, name, ".json").read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def module(self, kind: str, name: str):
        """The module ``<kind>/<name>.py``, loaded once."""
        path = self._find(kind, name, ".py")
        key = "bench_module_" + re.sub(r"\W", "_", str(path))
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            sys.modules[key] = mod
        return sys.modules[key]

    def reader(self, metric: str) -> Callable:
        return self.module("metrics", metric).read


def load_spec(path: Path = CHECKOUT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def cell_entry(spec: dict, cell: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"no workload named {cell!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones, or
    with ``trace`` its per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# set-up, window, check
# ---------------------------------------------------------------------------

def loud_warnings() -> tuple[type, ...]:
    from repro.kernels.ops import KernelFallbackWarning
    from repro.search import GuardWarning

    return (GuardWarning, KernelFallbackWarning)


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits, process-wide."""

    _instance = None

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        from jax import monitoring

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def snapshot(self) -> tuple[int, int]:
        """``(XLA compiles, persistent-cache loads)`` so far; JAX times a
        load as a compile too, so loads are taken off the compiles."""
        return self.compiles - self.cache_hits, self.cache_hits


WARMUP_BASE = 2**31 - 1    # warm-up batches come from the top of the range
TRACE_SECONDS = 20         # the traced window, at most


def _ready(x):
    return jax.block_until_ready(x) if isinstance(x, jax.Array) else x


def warm_up(serve, source, n: int) -> None:
    for j in range(n):
        ans = serve(_ready(source.batch(WARMUP_BASE - j)))
        for x in (ans.idx, ans.dists, ans.n_dtw, ans.degraded):
            np.asarray(x)


def pick_checks(reqs: list[Request], traffic: dict, seed: int
                ) -> list[tuple[int, int]]:
    """``(request, row)`` answers to compare with the reference: all of
    them, or ``check`` of them: the query that needed the most
    verifications, and the rest at batch rows spread evenly from the
    first to the last (rows 0, B/2 and B-1 of a batch of B for three),
    each in a request drawn from the seed.  So every check covers both
    halves of a batch and both of its ends."""
    every = [(r, j) for r in range(len(reqs)) for j in range(reqs[r].n)]
    want = traffic["check"]
    if want == "all" or want >= len(every):
        return every
    work = np.concatenate([reqs[r].n_dtw for r in range(len(reqs))])
    picks = [every[int(np.argmax(work))]]
    m = want - 1
    width = max(r.n for r in reqs)
    rows = np.rint(np.linspace(0, width - 1, m)).astype(int) if m > 1 \
        else np.zeros(m, int)
    rng = gen.seed_rng(seed, "check")
    for row in rows:
        free = [p for p in ((r, int(row)) for r in range(len(reqs))
                            if row < reqs[r].n) if p not in picks]
        if not free:
            free = [p for p in every if p not in picks]
        picks.append(free[int(rng.integers(len(free)))])
    return picks


def checked_queries(source, reqs, picks) -> jax.Array:
    """The picked queries, taken again from the traffic source."""
    batches = {reqs[r].i: np.asarray(source.batch(reqs[r].i))
               for r, _ in picks}
    return jnp.asarray(np.stack([batches[reqs[r].i][j] for r, j in picks]))


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(spec: dict, cell: str, seed: int, seconds: float,
             trace: bool, *, catalog: Catalog | None = None,
             t_start: float | None = None,
             serve_wrapper: Callable | None = None,
             control: bool = False) -> dict:
    """One run of ``cell``; returns the result object ``run.py`` prints.

    ``serve_wrapper(serve) -> serve`` wraps the served call (the fault
    tests break the timed path through it).  ``control`` also
    judges the control's answers to the same checked queries (the
    reference in bfloat16, ``check.control_answers``) and adds them to
    the result under ``control``; ``control.py`` reads them."""
    t_start = time.perf_counter() if t_start is None else t_start
    catalog = catalog or Catalog()
    entry = cell_entry(spec, cell)
    cfg = catalog.config(entry["config"])
    mix = catalog.traffic(entry["traffic"])
    limits = catalog.limits(cell)
    loop = catalog.module("loops", mix["loop"])
    loud = loud_warnings()
    counter = CompileCounter.get()

    with warnings.catch_warnings(record=True) as setup_caught:
        warnings.simplefilter("always")
        data = catalog.module("stores", cfg["store"]).make(cfg)
        serve = catalog.module("serve", cfg["serve"]).make(cfg, data)
        if serve_wrapper is not None:
            serve = serve_wrapper(serve)
        source = catalog.module("queries", mix["queries"]).make(
            mix, cfg, data, seed)
        warm_up(serve, source, mix.get("warmup", 1))
    setup_loud = [w for w in setup_caught if issubclass(w.category, loud)]
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {cell} seed {seed}, N={cfg['n_series']} "
        f"L={cfg['length']} w={cfg['w']} batch {mix['batch']}")

    win = seconds
    if trace:
        win = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = counter.snapshot()
    reqs, win_loud = loop.run(serve, source, win, mix["batch"], loud)
    c1 = counter.snapshot()
    if trace:
        jax.profiler.stop_trace()
    for w in setup_loud + win_loud:
        log(f"{w.category.__name__}: {w.message}")
    if setup_loud:
        for r in reqs:
            r.failed = True
    compiles, hits = c1[0] - c0[0], c1[1] - c0[1]
    log(f"window: {len(reqs)} requests, {sum(r.n for r in reqs)} queries "
        f"in {reqs[-1].t1 - reqs[0].t0:.3f} s; XLA compiles in window "
        f"{compiles}, persistent-cache loads in window {hits}")
    device = device_info(entry["chips"])

    summary = None
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    # the program's state goes before the reference runs
    del serve
    gc.collect()

    picks = pick_checks(reqs, mix, seed)
    queries = checked_queries(source, reqs, picks)
    served_i = np.stack([reqs[r].idx[j] for r, j in picks])
    served_d = np.stack([reqs[r].dists[j] for r, j in picks])
    t = time.perf_counter()
    numbers, per_answer, parts = _check.compare_with_reference(
        queries, data.store, cfg["w"], served_d, served_i)
    wrong = per_answer > limits["answer_gap"]
    log(f"reference: {len(picks)} answers in {time.perf_counter() - t:.3f} "
        f"s; worst dist_gap {parts['dist_gap']!r}, id_gap {parts['id_gap']!r}")
    for (r, _), bad in zip(picks, wrong):
        if bad:
            reqs[r].failed = True
    correct, checks = _check.judge(numbers, limits)
    control_out = None
    if control:
        ctl_d, ctl_i = _check.control_answers(queries, data.store, cfg["w"],
                                              k=served_i.shape[1])
        ctl_numbers, _, _ = _check.compare_with_reference(
            queries, data.store, cfg["w"], ctl_d, ctl_i)
        ctl_correct, ctl_checks = _check.judge(ctl_numbers, limits)
        control_out = {"correct": ctl_correct, "checks": ctl_checks}

    run = Run(cell=cell, config=cfg, traffic=mix, setup_s=setup_s,
              requests=reqs, trace=summary)
    metrics = {}
    for m in cell_metrics(spec, cell, trace):
        value = catalog.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {
        "correct": correct,
        "attempted": run.queries,
        "failed": sum(r.n for r in reqs if r.failed),
        "metrics": metrics,
        "device": device,
        "window": {"requests": len(reqs), "compiles": compiles,
                   "cache_loads": hits, "checked": len(picks)},
    }
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    if control_out is not None:
        out["control"] = control_out
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out
