#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip at
the cell's own size, in one process:

    python bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13 [--control-seeds 14,15,16] \\
        [--fault token_altered:17,half_batch:18]

Each seed is one whole run of the cell (``run.run_cell``: weights from
the seed, warm-up, the window, the comparison), as ``bench/run.py`` makes
it, in this one process.  A control seed's run judges the fp8 control in
the program's place (``bench/run.py --control 1``) and also reports the
program's own reading on the same sample.  A fault's run plants it
underneath the timed path (``bench/tests/faults.py``).  One JSON line per
run (its ``correct``, ``check`` and ``window``), then a summary: the
lower reading (the largest that sound runs give) and the smallest that
the control runs give.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cells as cells_lib  # noqa: E402
from bench import run  # noqa: E402
from bench.tests import faults  # noqa: E402


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="",
                    help="name:seed pairs, e.g. token_altered:17")
    args = ap.parse_args(argv)
    cell = cells_lib.load_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = run.require_devices(cell.chips)
    except run.NoDevice as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 3
    plan = ([("program", s) for s in _ints(args.seeds)]
            + [("control", s) for s in _ints(args.control_seeds)]
            + [(kv.split(":")[0], int(kv.split(":")[1]))
               for kv in args.fault.split(",") if kv])
    make = run.new_driver
    program, control = [], []
    for kind, seed in plan:
        run.new_driver = (make if kind in ("program", "control")
                          else faults.planted(run, faults.FAULTS[kind]))
        t0 = time.perf_counter()
        out = run.run_cell(cell, seed, args.seconds, False, devices,
                           t_process=t0, control=kind == "control")
        gap = out["check"]["mean_logit_gap"]["value"]
        if kind == "program":
            program.append(gap)
        elif kind == "control":
            control.append(gap)
            program.append(out["window"]["program_mean_logit_gap"])
        print(json.dumps({"kind": kind, "seed": seed,
                          "correct": out["correct"],
                          "metrics": out["metrics"], "window": out["window"],
                          "check": out["check"],
                          "seconds": time.perf_counter() - t0}), flush=True)
        gc.collect()
    run.new_driver = make
    print(json.dumps({"workload": args.workload,
                      "lower": max(program, default=None),
                      "upper": min(control, default=None),
                      "program_readings": len(program),
                      "control_readings": len(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
