"""Run one benchmark cell once, on the TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

(``python3 -m bench.run`` works too.)  Run from the root of a checkout.
The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
deployment, traffic mix, limits and metrics are files of their own under
``bench/`` (see ``harness.py``).  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
ones), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers the check compared beside their limits (``checks``), which also
end standard error.

Exits with 2, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the checkout has no ``src/repro``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)   # keep bench/'s modules from shadowing top-level ones
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _finite(obj):
    """JSON has no inf or nan: such a number is written as null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def fail(msg: str) -> int:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)
        return 2

    if not (ROOT / "src" / "repro").is_dir():
        return fail("no src/repro in this checkout: nothing to measure")
    from bench import harness

    spec = harness.load_spec(ROOT / "BENCHMARK.json")
    try:
        entry = harness.cell_entry(spec, args.workload)
    except KeyError as e:
        return fail(str(e))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX found {dev.platform!r} devices; the "
                    "benchmark measures only on the chip")
    if len(devices) < entry["chips"]:
        return fail(f"{args.workload} needs {entry['chips']} chips, JAX "
                    f"found {len(devices)}")

    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program goes to the persistent cache, however quick its
    # compile, so that only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
                f"compile cache {cache}")

    result = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
