"""Readings that the limits of ``limits/kimi_vl_a3b.train.json`` are set from.

    python3 chipbench/calibrate_vlm.py --workload kimi_vl_a3b.train \\
        --seeds 3 --control-seeds 2 --fault-seeds 1 --out <file.jsonl>

As ``calibrate.py``, for the VLM training driver (``drive_vlmtrain``), in
one process on one chip at the cell's own sizes. For each seed it drives
the cell as a run does, with a short window, and prints the numbers that
decide ``correct``. Then the control (the reference with its parameters
and residual stream in bfloat16, against the reference) on the first
``--control-seeds`` seeds, and each of ``drive_vlmtrain.FAULTS`` on the
first ``--fault-seeds``. Each row says whether it comes out ``correct`` under the
limits file as it stands (``Outcome.correct``); each is also written, one
JSON object a line, to ``--out``. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None, *, require_chip=True, overrides=None):
    import run as runmod
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=None,
                    help="comma-separated faults (default: all of drive_vlmtrain.FAULTS)")
    ap.add_argument("--first-seed", type=int, default=2_000_000_000)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)

    from harness import CompileCounter, Outcome, Run, checks, load_json, say
    bench = load_json(runmod.ROOT, "BENCHMARK.json")
    cell, cfg = runmod.find_cell(bench, a.workload)
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    limits = load_json(HERE, "limits", f"{cell['name']}.json")
    if overrides:
        cfg, traffic = overrides(cfg, traffic)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(runmod.ROOT, "src"))
    import jax
    dev = None
    if require_chip:
        dev, _ = runmod.accelerator(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    counter.install()
    driver = __import__(f"drive_{traffic['driver']}")
    faults = a.faults.split(",") if a.faults else driver.FAULTS
    rows = []

    def record(kind, seed, **row):
        row = dict(cell=cell["name"], kind=kind, seed=seed, **row)
        say(json.dumps(row))
        rows.append(row)
        if a.out:
            with open(a.out, "a") as fh:
                fh.write(json.dumps(row) + "\n")

    def emit(kind, seed, o, **extra):
        record(kind, seed, correct=o.correct,
               numbers={k: c["value"] for k, c in o.checks.items()}, **extra)

    def make_run(seed, fault=None):
        return Run(cell=cell["name"], cfg=cfg, traffic=traffic, limits=limits,
                   seed=seed, seconds=a.seconds, trace=False,
                   t_start=time.monotonic(), counter=counter,
                   faults=frozenset([fault] if fault else []), device=dev)

    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    for seed in seeds:
        t0 = time.monotonic()
        o = driver.run(make_run(seed))
        emit("program", seed, o, seconds=time.monotonic() - t0,
             memory_peak_bytes=o.memory_peak_bytes,
             images_per_s=o.e2e.get("train_images_per_s"))
    for seed in seeds[: a.control_seeds]:
        got = driver.control(cfg, traffic, seed)
        emit("control", seed, Outcome(window=None, attempted=0, failed=0,
                                      memory_peak_bytes=0,
                                      checks=checks(got, limits)))
    for seed in seeds[: a.fault_seeds]:
        for fault in faults:
            t0 = time.monotonic()
            try:
                o = driver.run(make_run(seed, fault))
            except Exception as e:      # a fault that cannot run is a reading too
                record(f"fault:{fault}", seed, error=f"{type(e).__name__}: {e}"[:500])
                continue
            emit(f"fault:{fault}", seed, o, seconds=time.monotonic() - t0)
    return rows


if __name__ == "__main__":
    main()
    sys.exit(0)
