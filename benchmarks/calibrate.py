"""Reads the numbers that a cell's limits are set from, on the chip.

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3,... [--control 3] [--fault 3] [--seconds 5]

One process. For each seed: the cell's driver is set up as in a run, a
short window is driven, the program's state is released, and the plain
reference is read once. Printed per seed: the gaps program-vs-reference
(the LOWER readings), and on the first ``--control`` seeds the gaps
control-vs-reference (the reference in the nearest lower precision, put
in the program's place: the UPPER readings), on the first ``--fault``
seeds the gaps of the reference with half of the batch left out. The
benchmark's own runs never run this. Lines also go to
``chiprun_out/calibrate_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run as bench_run


def small(readings: dict) -> dict:
    """The readings a line of JSON can carry: arrays are left out."""
    is_array = lambda v: hasattr(v, "shape") or (isinstance(v, list) and v and hasattr(v[0], "shape"))  # noqa: E731
    return {k: v for k, v in readings.items() if not is_array(v)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell, config, traffic = bench_run.load_cell(bench_run.load_benchmark(), args.workload)
    bench_run.check_device(cell["chips"])
    bench_run.enable_cache()
    reference = bench_run.load_module("references", cell["config"])
    driver_mod = bench_run.load_module("drivers", traffic["driver"])
    os.makedirs(os.path.join(bench_run.ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(bench_run.ROOT, "chiprun_out", f"calibrate_{args.workload}.jsonl"), "a")

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = driver_mod.Driver(config=config, traffic=traffic, seed=seed, reference=reference, log=bench_run.log)
        drv.setup()
        t_setup = time.perf_counter() - t0
        c0, c1, elapsed = bench_run.run_window(drv, args.seconds, None)
        e2e = drv.end_to_end(c0, c1, elapsed)
        drv.release()
        t1 = time.perf_counter()
        ref = drv.reference_readings()
        t_ref = time.perf_counter() - t1
        prog = drv.program_readings()
        line = {"seed": seed, "setup_s": t_setup, "reference_s": t_ref, "e2e": e2e,
                "program": drv.gaps(prog, ref),
                "readings": {"program": small(prog), "reference": small(ref)}}
        if i < args.control:
            got = drv.reference_readings(quant="fp8")
            line["control_fp8"] = drv.gaps(got, ref)
            line["readings"]["control_fp8"] = small(got)
        if i < args.fault:
            got = drv.reference_readings(fault="half_batch")
            line["fault_half_batch"] = drv.gaps(got, ref)
            line["readings"]["fault_half_batch"] = small(got)
        out.write(json.dumps(line) + "\n")
        out.flush()
        line.pop("readings")
        print(json.dumps(line), flush=True)
        del drv, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
