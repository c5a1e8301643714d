#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process owns.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. Each
is found by name: the configuration's sizes in ``bench/configs/<name>.json``
with its plain reference in ``bench/configs/<name>.py``; the traffic's
parameters in ``bench/traffic/<mix>.json``, whose ``kind`` names the
driver ``bench/drive_<kind>.py``; the per-layer metrics in
``bench/metrics/<metric>.py``; the correctness limits in
``bench/limits/<cell>.json``. Adding a cell adds files; it edits none.

Order of a run: fail unless the first JAX device is a TPU and the cell's
chips are there; keep the compile cache in the checkout; build weights
from ``--seed``; warm the cell's own executables; measure for
``--seconds``; compare what the timed path produced with the plain
reference; print the result as the last line of standard output, with the
numbers compared and their limits also as the last lines of standard
error. With ``--trace 1`` the window is traced and the per-layer metrics
are reported in place of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
import warnings

T_PROCESS = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_module(path: str, name: str):
    """Import a benchmark file by path (its name may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """Everything one cell is made of, found by name under ``bench/``."""

    def __init__(self, spec: dict, workload: str, bench_dir: str = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        self.spec = spec
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        conf = {c["name"]: c for c in spec["configs"]}[self.cell["config"]]
        cfg_file = os.path.join(os.path.dirname(bench_dir), conf["file"])
        self.config = read_json(cfg_file)
        self.config_module = load_module(
            os.path.splitext(cfg_file)[0] + ".py",
            "bench_config_" + conf["name"].replace("-", "_"))
        self.traffic = read_json(os.path.join(
            bench_dir, "traffic", self.cell["traffic"] + ".json"))
        self.driver = importlib.import_module(
            f"bench.drive_{self.traffic['kind']}")
        self.limits = read_json(os.path.join(bench_dir, "limits",
                                             workload + ".json"))
        self.end_to_end = [m for m in spec["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in spec["per_layer"]
                          if workload in m.get("workloads", [workload])]
        self.bench_dir = bench_dir

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        name + ".py"),
                           "bench_metric_" + name.replace(".", "_"))


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the first JAX device is {devs[0].platform!r}, not a "
                     "TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    d = devs[0]
    peak = 0
    for x in devs:
        st = x.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def execute(cell: Cell, seed: int, seconds: float, trace: bool, devs,
            out_dir: str, t0: float = T_PROCESS, **driver_kw) -> dict:
    """Drive the cell once on ``devs`` and build the result line; set-up
    is counted from ``t0``."""
    run = cell.driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                          devices=devs, out_dir=out_dir, t0=t0, log=say,
                          device_info=device_info, **driver_kw)
    checks = run["checks"]
    correct = all(c["ok"] for c in checks) and run["failed"] == 0
    if trace:
        ctx = run["context"]
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = run["device"]
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = run["breakdown"]
    result["checks"] = [{k: c[k] for k in ("name", "value", "limit")}
                        for c in checks]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the persistent compile cache lives in the checkout at a fixed path,
    # set before anything imports JAX; the program takes the variable
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(spec, args.workload)
    try:
        devs = check_device(cell.chips)
    except NoChip as e:
        say(f"FAILED: {e}")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    say(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}; compile cache {enable_compile_cache()}")
    # a kernel-gate fallback would time the jnp path instead of the kernel
    warnings.filterwarnings("error", message="flash_attention: kernel gate")
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                     out_dir)
    say(f"correct {result['correct']}")
    for c in result["checks"]:
        say(f"check {c['name']} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
