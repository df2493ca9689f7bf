#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload kdda.minibatch --seed 7 --seconds 10 --trace 0

Loads the cell named in ``BENCHMARK.json`` (its configuration from
``bench/configs/``, its traffic from ``bench/traffic/``, its limits from
``bench/workloads/``), makes the data from ``--seed``, builds and warms
the program, measures for ``--seconds``, checks the first epochs against
the plain reference, and prints one JSON line: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window. It exits non-zero, printing no
result, without a TPU or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402


class NoChip(RuntimeError):
    """The machine lacks what the cell needs."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration,
    traffic, limits and metric lists."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cell = dict(load_json(BENCH / "workloads" / f"{name}.json"))
    cell.update(name=name, chips=entry["chips"],
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(BENCH / "traffic"
                                  / f"{entry['traffic']}.json"))

    def mine(metric):
        return name in metric.get("workloads", [name])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if (name in m["workloads"] if "workloads" in m
                             else m["moves"] in reported)]
    return cell


def chips(n: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX sees {len(devices)}")
    return devices[:n]


def peak_table(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"device {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def traced_window(driver, run, seconds: float, devices) -> tuple:
    """The window under the profiler; returns (window result, reduced
    trace)."""
    import jax

    import tracing
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            res = driver.window(run, seconds)
        jax.profiler.stop_trace()
        reduced = tracing.reduce(tracing.load(tmp), [d.id for d in devices],
                                 driver.hlo_text(run))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return res, reduced


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            devices) -> dict:
    """Set-up, window, check; returns the result line as a dict."""
    import jax.numpy as jnp
    driver = importlib.import_module(f"drivers.{cell['traffic']['driver']}")
    run = driver.setup(cell, seed)
    setup_s = time.perf_counter() - T_START
    if trace:
        res, reduced = traced_window(driver, run, seconds, devices)
    else:
        res, reduced = driver.window(run, seconds), None
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    shapes = driver.kernel_shapes(run)

    driver.drop_program(run)
    ref = driver.reference_run(run, jnp.float32)
    verdict = check.judge(check.gaps(run.checked, ref), cell["limits"])

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        ctx = {"trace": reduced, "units": res["units"], "cell": cell,
               "peaks": peak_table(dev.device_kind), "shapes": shapes}
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
    else:
        values = dict(res, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    line = {"correct": verdict["correct"], "attempted": res["units"],
            "failed": verdict["failed"], "metrics": metrics,
            "device": device}
    if trace:
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = verdict["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    cell = load_cell(args.workload)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program the run compiles goes to the cache, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    peak_table(devices[0].device_kind)
    line = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    check.report(line["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
