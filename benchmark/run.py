#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs ONE cell of BENCHMARK.json once in this process (and the processes its
driver starts), and prints the result as one JSON object on the last line of
standard output. It knows no cell, model, mix or metric by name: a cell names
a configuration (benchmark/configs/<config>.json) and a traffic mix
(benchmark/traffic/<traffic>.json); the mix names its driver
(benchmark/drivers/<driver>.py); each per-layer metric has a reader
(benchmark/layer_metrics/<name>.py, or <family>.py for ``family.member``);
the limits of ``correct`` are in benchmark/limits/<workload>.json. A later PR
adds files and one entry, and edits nothing here.

Exit codes: 0 a result line was printed; 2 bad arguments or files; 3 no
accelerator, an unknown device kind or too few chips (never a CPU number);
4 the system under test is not in the checkout; 1 anything else.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def load_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader_path(metric: str, bench_dir: str) -> str:
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(bench_dir, "layer_metrics", stem + ".py")
        if os.path.exists(path):
            return path
    raise Refused(2, f"no reader benchmark/layer_metrics/{metric}.py for per-layer metric {metric!r}")


def metrics_of(cell_name: str, entries: list[dict]) -> list[dict]:
    return [m for m in entries if "workloads" not in m or cell_name in m["workloads"]]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_file: str | None = None, require_chip: bool = True,
             extra: dict | None = None) -> dict:
    """One run of one cell; returns the result object. ``require_chip=False``
    is for the tests under benchmark/tests, which drive the whole of a run at
    test sizes on whatever JAX finds; ``extra`` reaches the driver as
    ``ctx['extra']`` (limit-setting runs ask for the control through it)."""
    bench_file = bench_file or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_file)
    base = os.path.dirname(os.path.abspath(bench_file))
    bench_dir = os.path.join(base, bench["paths"][0])
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise Refused(2, f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(base, config_entry["file"]))
    mix = load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    limits_path = os.path.join(bench_dir, "limits", workload + ".json")
    limits = load_json(limits_path)["limits"] if os.path.exists(limits_path) else {}
    if not os.path.isdir(os.path.join(ROOT, "tony_tpu")):
        raise Refused(4, f"the system under test (tony_tpu/) is not in {ROOT}")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    # one fixed cache directory inside the checkout, unless one is given
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    work = os.path.join(ROOT, ".bench_work", workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    ctx = {
        "cell": cell, "config": config, "mix": mix, "seed": int(seed),
        "seconds": float(seconds), "trace": bool(trace), "root": ROOT,
        "bench_dir": HERE, "work": work, "peaks": peaks, "t_start": T_START,
        "require_chip": require_chip, "refuse": Refused, "extra": extra or {},
        "limits": limits,
    }
    driver = load_module(os.path.join(HERE, "drivers", mix["driver"] + ".py"))
    out = driver.run(ctx)  # e2e, observed, device, attempted, failed, numbers, trace_dir

    device = out["device"]
    if require_chip:
        check_device(device, cell, peaks)
    from benchmark import compare

    correct, rows = compare.verdict(out["numbers"], limits)
    correct = bool(correct and limits and out["failed"] == 0)
    result_metrics: dict = {}
    breakdown = None
    if trace:
        from benchmark import trace_reduce

        path = trace_reduce.find_trace(out["trace_dir"]) if out.get("trace_dir") else None
        reduced = trace_reduce.reduce(trace_reduce.load(path)) if path else None
        if reduced is None or reduced["busy_s"] <= 0:
            raise Refused(1, "the traced run holds no device operation")
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        rctx = {**ctx, "observed": out["observed"], "e2e": out["e2e"], "trace": reduced,
                "peak": peaks.get(device["kind"])}
        for m in metrics_of(workload, bench["per_layer"]):
            value = load_module(reader_path(m["name"], HERE)).read(m["name"], rctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(workload, bench["end_to_end"]):
            if m["name"] not in out["e2e"]:
                raise Refused(1, f"driver {mix['driver']} gave no {m['name']}")
            result_metrics[m["name"]] = {"value": out["e2e"][m["name"]], "unit": m["unit"]}
    result = {
        "correct": correct, "attempted": out["attempted"], "failed": out["failed"],
        "metrics": result_metrics, "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    if out.get("notes"):
        result["notes"] = out["notes"]
    result["checks"] = rows  # each number compared beside its limit: last
    return result


def check_device(device: dict, cell: dict, peaks: dict) -> None:
    if device.get("platform") != "tpu":
        raise Refused(3, f"JAX found no accelerator: platform {device.get('platform')!r}")
    if device.get("kind") not in peaks:
        raise Refused(3, f"device kind {device.get('kind')!r} is not in benchmark/peaks.json")
    if device.get("count", 0) < cell["chips"]:
        raise Refused(3, f"cell needs {cell['chips']} chip(s), JAX sees {device.get('count')}")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--extra", default="", help="JSON for the driver (limit-setting runs)")
    args = p.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          extra=json.loads(args.extra) if args.extra else None)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    for row in result["checks"]:
        print(f"check {row['name']}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
