"""The benchmark's command: one process, one cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, measure for ``--seconds``, check what the timed path
produced against the configuration's plain reference, print ONE JSON line
last on standard output. Everything that belongs to one cell is found by
the names in ``BENCHMARK.json``: ``configs/<config>.json``,
``traffic/<traffic>.json`` (which names its ``driver``),
``drivers/<driver>.py``, ``references/<config>.py`` and
``layer_metrics/<metric>.py``. This file holds no cell's name.

No accelerator, fewer chips than the cell asks for, or a device that is
not in ``harness/peaks.py``: exit code 2 and no result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import peaks as peaks_mod  # noqa: E402
from harness import trace as trace_mod  # noqa: E402


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold '-' and '.')."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(the cell's entry, its configuration as run, its traffic mix)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    return cell, config, load_json("traffic", cell["traffic"])


def metrics_for(entries: list, cell: str) -> list:
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def check_device(chips: int) -> dict:
    """The device as JAX reports it and its peaks; exits with code 2 where
    this is not the machine the cell asks for. Never a CPU run."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] == "cpu":
        log(f"no accelerator: jax found {info}")
        sys.exit(2)
    if info["count"] < chips:
        log(f"the cell asks for {chips} chips, jax found {info['count']}")
        sys.exit(2)
    try:
        pk = peaks_mod.device_peaks(info["kind"])
    except KeyError as e:
        log(str(e))
        sys.exit(2)
    info["count"] = chips
    return {"info": info, "peaks": pk}


def memory_peak_bytes(chips: int) -> int:
    """The runtime's ``peak_bytes_in_use`` on the fullest chip, read when
    the window has closed and before the reference runs. On this runtime it
    leaves XLA's scratch out, so it is a lower bound (PERF.md, Open
    questions)."""
    import jax

    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:chips]))


def enable_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache`` (the program's own rule); every program is
    kept, also those that compile in under a second."""
    import jax
    from rl_tpu.config import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_window(driver, seconds: float, trace_dir: str | None):
    """Drive ``driver.unit()`` until ``seconds`` have passed, ending on a
    unit's boundary with the device drained. Returns (counters at start,
    at end, elapsed seconds)."""
    import jax
    from jax.profiler import TraceAnnotation

    c0 = driver.counters()
    if trace_dir:
        # the device's ops and the benchmark's own host spans, nothing else:
        # the Python tracer alone writes millions of events a window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with TraceAnnotation(trace_mod.WINDOW_SPAN):
        driver.open_window(t0)
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation(driver.span):
                driver.unit()
        with TraceAnnotation("bench.drain"):
            driver.close_window()
        elapsed = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    return c0, driver.counters(), elapsed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    try:
        cell, config, traffic = load_cell(bench, args.workload)
    except KeyError as e:
        log(e.args[0])
        return 2
    dev = check_device(cell["chips"])
    cache_dir = enable_cache()

    import jax
    from rl_tpu.compile import compile_counts, compile_seconds_total, install_compile_listener

    install_compile_listener()
    reference = load_module("references", cell["config"])
    driver = load_module("drivers", traffic["driver"]).Driver(
        config=config, traffic=traffic, seed=args.seed, reference=reference, log=log
    )
    driver.setup()
    compile_setup, counts_setup = compile_seconds_total(), compile_counts()

    trace_dir = None
    seconds = args.seconds
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, float(traffic.get("trace_seconds", 5.0)))
    setup_s = time.perf_counter() - _T0
    c0, c1, elapsed = run_window(driver, seconds, trace_dir)
    compile_window = compile_seconds_total() - compile_setup
    compiled = {k: v - counts_setup.get(k, 0) for k, v in compile_counts().items() if v > counts_setup.get(k, 0)}
    if compiled:
        log(f"compiled or loaded inside the window: {compiled}")
    log(f"setup_s={setup_s:.3f} compile_in_setup_s={compile_setup:.3f} "
        f"compile_in_window_s={compile_window:.3f} window_s={elapsed:.3f} cache={cache_dir}")
    peak = memory_peak_bytes(cell["chips"])
    device = dict(dev["info"], memory_peak_bytes=peak)

    measured = driver.end_to_end(c0, c1, elapsed)
    measured["setup_s"] = setup_s
    metrics: dict = {}
    breakdown = None
    if not args.trace:
        for m in metrics_for(bench["end_to_end"], args.workload):
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    else:
        t_read = time.perf_counter()
        red = trace_mod.reduce_file(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"reading the trace took {time.perf_counter() - t_read:.3f} s")
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
        run = {
            "config": config, "traffic": traffic, "peaks": dev["peaks"], "trace": red,
            "c0": c0, "c1": c1, "elapsed": elapsed, "driver": driver, "log": log,
        }
        for m in metrics_for(bench["per_layer"], args.workload):
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read in this run")
            else:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # the program's state goes before the reference comes: a process's
    # memory peak never falls again, so it was read above
    driver.release()
    t_check = time.perf_counter()
    compared = driver.check()
    log(f"reference and comparison took {time.perf_counter() - t_check:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    result = {
        "correct": bool(correct),
        "attempted": int(measured["attempted"]),
        "failed": int(measured["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: value={c['value']:.6g} limit={c['limit']:.6g}")
    log(f"correct={correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
