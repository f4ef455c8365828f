#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload qft28.state --seed 7 --seconds 10 --trace 0

From the root of a checkout. The cell, its configuration, traffic and
metrics are named in ``BENCHMARK.json``. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window. Every run checks the states the window produced
against the configuration's plain reference and prints each number
compared beside its limit (``check ...`` lines on stderr, and ``checks``,
the result's last key). Needs a TPU with as many chips as the cell asks
for; without one, or without the simulator next to this directory, it
exits non-zero and prints no result.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return _fail("the simulator (src/repro) is not in this checkout", 2)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # plans come from committed code only, never a calibration file
    os.environ["REPRO_CALIBRATION"] = "off"
    # libtpu's own log files would go to /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench.harness import Cell, run_cell

    cell = Cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return _fail(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < cell.chips:
        return _fail(f"{cell.chips} chips asked for, {len(devs)} present")
    # a fixed path inside the checkout, so only a checkout's first run
    # compiles and two checkouts share nothing; no size cap (a machine's
    # JAX_COMPILATION_CACHE_MAX_SIZE may hold less than one stage program,
    # which would then compile in every run)
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
