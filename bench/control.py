"""Readings for the limits of ``correct``, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds 5 [--out FILE]

For each seed, one whole run of the cell (set-up, a window of
``--seconds``, the check) in this one process, which reads the numbers
the program's answers give.  On the control seeds the same checked
queries are also answered by the control, the plain reference computed
one precision step down (bfloat16 for the float32 the deployments
state), and judged the same way.  Prints one JSON line per seed, and a
last line with, per number, the largest reading of the program and the
smallest of the control: the lower and upper readings a limit is set
between.  Refuses to run without a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s.strip()]


def readings(spec, cell, seeds, control_seeds, seconds, **kw):
    """Per-seed results, and the lower/upper reading of each number."""
    from bench import check, harness

    rows = []
    for seed in seeds:
        out = harness.run_cell(spec, cell, seed, seconds, False,
                               control=seed in control_seeds, **kw)
        row = {"seed": seed, "correct": out["correct"],
               "program": {k: v["value"] for k, v in out["checks"].items()}}
        if "control" in out:
            row["control"] = {k: v["value"]
                              for k, v in out["control"]["checks"].items()}
            row["control_correct"] = out["control"]["correct"]
        rows.append(row)

    def worst(vals, pick):
        vals = [float("inf") if v is None else v for v in vals]
        return pick(vals) if vals else None

    summary = {}
    for name in check.NUMBERS:
        summary[name] = {
            "lower": worst([r["program"][name] for r in rows], max),
            "upper": worst([r["control"][name] for r in rows
                            if "control" in r], min),
        }
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("[bench] no TPU: control readings come only from the chip",
              file=sys.stderr)
        return 2
    from bench import harness
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = harness.load_spec(ROOT / "BENCHMARK.json")
    rows, summary = readings(spec, args.workload, args.seeds,
                             set(args.control_seeds), args.seconds)
    lines = [json.dumps(r) for r in rows] + [json.dumps(
        {"workload": args.workload, "readings": summary})]
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    print("\n".join(lines), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
