"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

One process, one chip, the cell's own sizes. For each seed it drives the
cell as a run does, with a short window (its numbers come from the first
steps), and prints the numbers that decide ``correct``. Then, on
the first ``--control-seeds`` seeds, it prints the same numbers for

* the control: the plain reference put in the program's place in bfloat16,
  one precision below the configuration's float32, against the reference;
* each fault planted in the program: half of each batch left out (the mean
  taken over the rest), the state returned unchanged, and the update
  applied with its sign turned.

Each row also says whether it comes out ``correct`` under the limits file
as it stands, by the harness's own ``Outcome.correct``. The lower reading of
a number is the largest over the program's seeds, the upper the smallest
over the control's and the faults'. The benchmark's own runs never run
this. Each reading is also written, one JSON object a line, to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

FAULTS = ("half_batch", "state_unchanged", "update_negated")


def main(argv=None, *, require_chip=True, overrides=None):
    import run as runmod
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
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
    out = open(a.out, "w") if a.out else None

    def emit(kind, seed, o, **extra):
        numbers = {k: c["value"] for k, c in o.checks.items()}
        row = dict(cell=cell["name"], kind=kind, seed=seed,
                   correct=o.correct, numbers=numbers,
                   worst=o.info.get("grad_worst_leaves"), **extra)
        say(json.dumps(row))
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    def make_run(seed, faults=frozenset()):
        return Run(cell=cell["name"], cfg=cfg, traffic=traffic, limits=limits,
                   seed=seed, seconds=a.seconds, trace=False,
                   t_start=time.monotonic(), counter=counter,
                   faults=frozenset(faults), device=dev)

    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    for seed in seeds:
        t0 = time.monotonic()
        o = driver.run(make_run(seed))
        emit("program", seed, o, seconds=time.monotonic() - t0)
    for seed in seeds[: a.control_seeds]:
        got = control(driver, cfg, traffic, seed)
        emit("control", seed, Outcome(window=None, attempted=0, failed=0,
                                      memory_peak_bytes=0,
                                      checks=checks(got, limits)))
        for fault in FAULTS:
            emit(f"fault:{fault}", seed, driver.run(make_run(seed, {fault})))
    if out:
        out.close()
    return 0


def control(driver, cfg, traffic, seed):
    """The control's numbers on one seed."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    import generate
    import reference
    from harness import seed_key
    params = jax.jit(partial(reference.init_params, cfg))(seed_key(seed))
    pool = generate.train_pool(cfg, traffic, seed)
    ref = driver.reference_steps(cfg, traffic, pool, params)
    low = driver.reference_steps(cfg, traffic, pool, params, dtype=jnp.bfloat16)
    return driver.numbers(low, ref)


if __name__ == "__main__":
    sys.exit(main())
