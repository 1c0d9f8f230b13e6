"""Run one cell of the chip benchmark and print its result as one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration file,
its traffic file (``traffic/<traffic>.json``) and its limits
(``limits/<cell>.json``) are found by name, and the traffic file's ``driver``
names the module that drives it (``drive_<driver>.py``). With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` each
per-layer metric of the cell, read by its own reader,
``metrics/<metric>.py``. A traced run measures the untraced window first and
then has the profiler record the traffic's ``trace_seconds`` more.

The run needs a TPU: it exits non-zero, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for, or where the device is not in
``peaks.json``. The last lines on standard error, and the ``checks`` key of
the result, give each number that decided ``correct`` beside its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gzip  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import CompileCounter, Run, check_lines, load_json, say, span  # noqa: E402


class NoDevice(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_cell(bench, name):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, load_json(ROOT, cfg_entry["file"])


def metric_reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def accelerator(chips: int):
    """The first device and the peaks row of its kind; no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (first device: {devices[0].platform})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    peaks = load_json(HERE, "peaks.json")["devices"]
    if devices[0].device_kind not in peaks:
        raise NoDevice(f"{devices[0].device_kind!r} is not in peaks.json")
    return devices[0], peaks[devices[0].device_kind]


def per_layer(bench, cell, r: Run, outcome, peak, keep=None):
    """Reduce the trace and read each per-layer metric of the cell."""
    import tracereduce
    path = tracereduce.find(r.trace_dir())
    if keep:
        with open(path, "rb") as src, gzip.open(f"{keep}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        with open(f"{keep}.spans.json", "w") as fh:
            json.dump(span.events, fh)
    red = tracereduce.reduce(tracereduce.load(path, span.events))
    ctx = dict(cell=cell, cfg=r.cfg, traffic=r.traffic, peak=peak,
               outcome=outcome, trace=red)
    metrics = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    shutil.rmtree(r.trace_dir(), ignore_errors=True)
    return metrics, red


def main(argv=None, *, faults=frozenset(), require_chip=True, overrides=None,
         keep_trace=None):
    """``keep_trace``: a path prefix to keep the trace and the host spans
    at (``.xplane.pb.gz``, ``.spans.json``), for the tests."""
    a = parse(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg = find_cell(bench, a.workload)
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    limits = load_json(HERE, "limits", f"{cell['name']}.json")
    if overrides:
        cfg, traffic = overrides(cfg, traffic)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    dev, peak = None, None
    if require_chip:
        try:
            dev, peak = accelerator(cell["chips"])
        except NoDevice as e:
            say(f"error: {e}")
            return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    counter.install()
    r = Run(cell=cell["name"], cfg=cfg, traffic=traffic, limits=limits,
            seed=a.seed, seconds=a.seconds, trace=bool(a.trace),
            t_start=T_START, counter=counter, faults=frozenset(faults),
            device=dev)
    driver = importlib.import_module(f"drive_{traffic['driver']}")
    say(f"cell {cell['name']}: config {cfg['name']}, traffic {cell['traffic']}, "
        f"seed {a.seed}, compile cache {cache}")
    outcome = driver.run(r)
    win = outcome.window
    say(f"set-up {win.setup_s:.3f} s with {win.compiles_setup} programs "
        f"compiled or loaded from the cache ({counter.compiled} compiled in "
        f"the whole run); window {win.seconds:.3f} s, {win.compiles_window} "
        f"programs obtained in it")

    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    e2e = dict(outcome.e2e, setup_s=win.setup_s)
    if a.trace:
        metrics, red = per_layer(bench, cell, r, outcome, peak, keep_trace)
        result["breakdown"] = red["breakdown"]
    else:
        metrics = {}
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    d = dev or jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    if a.trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    result["device"] = device
    result["compiles_in_window"] = win.compiles_window
    result["info"] = outcome.info
    result["checks"] = outcome.checks
    for line in check_lines(outcome.checks):
        say(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
