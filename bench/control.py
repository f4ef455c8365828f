#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python3 bench/control.py --workload su2random28.state \\
        --seeds 1,2,...,12 --control-seeds 101,102,103

For each of ``--seeds`` the simulator, built once as the configuration
states, runs the input that the benchmark's window draws first for that
seed, and its state is compared with the reference: the lower readings.
For each of ``--control-seeds`` the configuration's ``control`` reference,
computed in the precision below the stated one (three bf16 passes per
product, ``Precision.HIGH``), takes the simulator's place: the upper
readings. (The simulator itself has no lower-precision path to serve as
the control: Mosaic lowers a Pallas dot only at ``DEFAULT`` or ``HIGHEST``.)
One JSON line per reading, then a summary line. Needs the chip; the
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def first_input(system, cell, seed: int) -> int:
    """The x of the window's first simulation for ``seed``."""
    import importlib

    driver = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    loop = driver.Loop(system, cell.traffic, seed)
    loop.next_x()  # the warm-up's draw
    return loop.next_x()


def readings(cell, seeds, control_seeds, *, emit=print) -> dict:
    """``{"program": [...], "control": [...]}``: the comparison's numbers
    for each seed, as ``compare.errors`` gives them."""
    import importlib

    from bench.compare import errors
    from bench.harness import Spans

    gates = cell.gates()
    n = cell.config["circuit"]["n_qubits"]
    ref = cell.reference()
    control = importlib.import_module(f"bench.references.{cell.config['control']}")
    system = cell.system(gates, Spans())
    out = {"program": [], "control": []}
    for kind, seed in ([("program", s) for s in seeds]
                       + [("control", s) for s in control_seeds]):
        x = first_input(system, cell, seed)
        if kind == "program":
            got = system.run(system.make_input(x))
        else:
            got = control.state(gates, n, x, precision="high")
        e = errors(got, ref.state(gates, n, x))
        del got
        out[kind].append(e)
        emit(json.dumps({kind: seed, "x": x, **e}), flush=True)
    summary = {kind: {k: {"min": min(r[k] for r in rs), "max": max(r[k] for r in rs)}
                      for k in rs[0]} for kind, rs in out.items() if rs}
    emit(json.dumps({"summary": summary, "workload": cell.name}), flush=True)
    return out


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["REPRO_CALIBRATION"] = "off"
    # libtpu's own log files would go to /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench.harness import Cell

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    readings(Cell(args.workload), args.seeds, args.control_seeds)
    print(f"control: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
