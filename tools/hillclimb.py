"""Hillclimb driver with two modes.

Roofline mode (default): compile ONE dry-run cell with config/rule overrides
and print roofline terms + an HLO byte/op profile (the CPU-only 'profiler').

  PYTHONPATH=src python tools/hillclimb.py --arch gemma2-9b --shape decode_32k \
      [--set swa_ring_buffer=True] [--rule expert_cap=pod,data] [--profile]

DSE mode (--dse): greedy local search over the paper's design space
{arch x node x variant x NVM device x PE config} for one workload, driven by
the experiment API — every candidate neighborhood is a ``DesignSpace`` and
all structural work is memoized by one ``Evaluator``, so each step prices a
handful of cached mappings instead of re-running the pipeline.

  PYTHONPATH=src python tools/hillclimb.py --dse --workload detnet \
      [--objective edp|energy|pmem] [--ips 10]

System mode (--system): the same greedy search on the MULTI-STREAM plane
(core.schedule): a bundle of concurrent workloads time-shared on one
accelerator, moving (arch, node, pe_config, contention mode, per-level
placement) to minimize feasible system memory power.

  PYTHONPATH=src python tools/hillclimb.py --system \
      [--stream detnet=10 --stream edsnet=0.1]
"""
import argparse
import collections
import contextlib
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def parse_override(s):
    k, v = s.split("=", 1)
    with contextlib.suppress(Exception):
        v = eval(v, {}, {})
    return k, v


def profile_hlo(hlo: str, top: int = 18):
    """Aggregate result-shape bytes by opcode + biggest single ops."""
    from repro.core import roofline as rl

    by_op = collections.Counter()
    biggest = []
    for line in hlo.splitlines():
        parsed = rl.parse_op(line)
        if parsed is None:
            continue
        shape, op = parsed
        b = rl._shape_bytes(shape)
        by_op[op] += b
        biggest.append((b, op, shape[:60]))
    print("\n-- bytes by opcode (result shapes, per-device HLO) --")
    for op, b in by_op.most_common(top):
        print(f"   {op:<28}{b/1e9:10.2f} GB")
    print("-- biggest single ops --")
    for b, op, shape in sorted(biggest, reverse=True)[:8]:
        print(f"   {b/1e9:8.2f} GB  {op:<20}{shape}")


# ---------------------------------------------------------------------------
# DSE mode: greedy local search over the experiment design space
# ---------------------------------------------------------------------------

# The move generators live in repro.search.moves (shared with the
# population optimizer); these module-level names are the stable import
# surface the tests and the system mode use.
def _moves():
    from repro.search import moves
    return moves


def _arch_move(point, arch_name):
    return _moves().arch_move(point, arch_name)


def placement_moves(point, techs=None):
    return _moves().placement_moves(point, techs)


def __getattr__(name):
    if name == "DSE_AXES":
        return _moves().DSE_AXES
    raise AttributeError(name)


def dse_main(a):
    """Greedy local search on the COLUMNAR path (repro.search.moves.greedy):
    every neighborhood is one ``EnergyTable`` pricing (a single vectorized
    pass over ~30 points) and the objective is a table column — no
    per-point report objects."""
    from repro.core.experiment import Evaluator
    from repro.core.space import DesignPoint
    from repro.search.moves import greedy

    if a.objective == "edp":
        metric = "edp"
        fmt = lambda v: f"edp={v:.3e} J*s"
    elif a.objective == "energy":
        metric = "total_pj"
        fmt = lambda v: f"E={v/1e6:.2f} uJ"
    else:
        metric = "pmem"
        fmt = lambda v: f"P_mem@{a.ips}ips={v*1e6:.1f} uW"

    ev = Evaluator()
    start = DesignPoint(workload=a.workload, arch="cpu", node=45,
                        variant="sram")
    t0 = time.monotonic()
    print(f"=== DSE hillclimb: {a.workload}, objective {a.objective} ===")

    def on_step(step, p, v):
        print(f"  step {step}: {p.arch}/{p.node}nm/{p.variant}"
              f"/{p.nvm or 'auto'}/{p.pe_config}/{p.precision_label}"
              f"  {fmt(v)}")

    p, val, steps = greedy(ev, start, metric=metric, ips=a.ips,
                           on_step=on_step)
    table = ev.evaluate_table([p])
    hits, misses = ev.cache_info()["traffic"]
    print(f"\nlocal optimum after {steps} steps "
          f"({time.monotonic()-t0:.1f}s, traffic cache {hits}h/{misses}m):")
    print(f"  {p.arch} @ {p.node}nm, {p.variant}/{p.nvm or 'auto'}, "
          f"pe={p.pe_config}, {p.precision_label}: {fmt(val)}  "
          f"lat={float(table.latency_s[0])*1e3:.2f}ms  "
          f"E={float(table.total_pj[0])/1e6:.2f}uJ")


# ---------------------------------------------------------------------------
# system mode: greedy search over the multi-stream plane (core.schedule)
# ---------------------------------------------------------------------------

SYSTEM_AXES = dict(
    node=(45, 40, 28, 22, 7),
    pe_config=("v1", "v2"),
    mode=("reload", "union"),
)


def parse_streams(specs):
    """``["detnet=10", "edsnet=0.1"]`` -> Stream tuple."""
    from repro.core.schedule import Stream

    out = []
    for s in specs:
        name, _, ips = s.partition("=")
        if not ips:
            raise ValueError(f"--stream {s!r}: want WORKLOAD=IPS")
        out.append(Stream(name.strip(), float(ips)))
    return tuple(out)


def system_main(a):
    """Greedy local search over the SYSTEM design space: the stream bundle
    stays fixed, (arch, node, pe_config, contention mode, per-level
    placement) move. Each neighborhood is ONE ``SystemTable`` pricing;
    infeasible systems (sum of duties > 1) are never selected."""
    import numpy as np

    from repro.core.experiment import XR_BUNDLE, Evaluator
    from repro.core.schedule import SystemPoint
    from repro.search.moves import DSE_AXES

    streams = parse_streams(a.stream) if a.stream else XR_BUNDLE
    ev = Evaluator()

    def best_of(points):
        tab = ev.system_table(points)
        vals = np.where(tab.feasible, tab.p_mem_w, np.inf)
        i = int(np.argmin(vals))
        return points[i], float(vals[i]), (tab, i)

    point = SystemPoint(streams, "simba", 45, "sram")
    best = best_of([point])
    if not np.isfinite(best[1]):
        raise SystemExit(f"stream bundle {[s.name for s in streams]} is "
                         f"infeasible even on the starting system")
    label = "+".join(f"{s.name}@{s.ips:g}" for s in streams)
    print(f"=== system hillclimb: {label}, objective P_mem ===")
    t0 = time.monotonic()
    step = 0
    while True:
        cur = best[0]
        neighbors = [cur.with_(**{axis: v})
                     for axis, values in SYSTEM_AXES.items()
                     for v in values if v != getattr(cur, axis)]
        neighbors += [_arch_move(cur, v) for v in DSE_AXES["arch"]
                      if v != cur.arch]
        neighbors += placement_moves(cur)
        cand = best_of([cur] + neighbors)
        if cand[1] >= best[1]:
            break
        best = cand
        step += 1
        p = best[0]
        print(f"  step {step}: {p.arch}/{p.node}nm/{p.mode}/{p.variant}"
              f"  P_mem={best[1]*1e6:.1f} uW")
    p, val, (tab, i) = best
    print(f"\nlocal optimum after {step} steps "
          f"({time.monotonic()-t0:.1f}s):")
    print(f"  {p.arch} @ {p.node}nm, mode={p.mode}, {p.variant}: "
          f"P_mem={val*1e6:.1f} uW  duty={float(tab.duty[i]):.4f}  "
          f"reload={float(tab.reload_w[i])*1e6:.2f} uW")


# ---------------------------------------------------------------------------
# roofline mode (dry-run compile probe)
# ---------------------------------------------------------------------------

def roofline_main(a):
    from repro.configs import SHAPES, get_config
    from repro.core import roofline as rl
    from repro.launch import dryrun, mesh as mesh_mod
    from repro.models import lm as lm_mod

    dryrun.force_host_devices()
    cfg = get_config(a.arch)
    if a.set:
        cfg = dataclasses.replace(cfg, **dict(parse_override(s) for s in a.set))
    mesh = mesh_mod.make_production_mesh(multi_pod=a.multi_pod)
    rules = mesh_mod.shape_rules(cfg, a.shape) or {}
    for r in a.rule:
        k, v = r.split("=", 1)
        rules[k] = tuple(v.split(",")) if v else None

    R_full = lm_mod.num_repeats(cfg)
    t0 = time.monotonic()
    dryrun._compile_cell(cfg, a.shape, mesh, rules)  # full-config check
    c1 = dryrun._costs(dryrun._compile_cell(
        dryrun._scaled_cfg(cfg, 1, enc_layers=1), a.shape, mesh, rules))
    c2c = dryrun._compile_cell(dryrun._scaled_cfg(cfg, 2, enc_layers=1),
                               a.shape, mesh, rules)
    c2 = dryrun._costs(c2c)
    cost = [c1[i] + (c2[i] - c1[i]) * (R_full - 1) for i in range(3)]
    if cfg.encoder_layers > 1:
        c1e = dryrun._costs(dryrun._compile_cell(
            dryrun._scaled_cfg(cfg, 1, enc_layers=2), a.shape, mesh, rules))
        for i in range(3):
            cost[i] += (c1e[i] - c1[i]) * (cfg.encoder_layers - 1)
    n = mesh.devices.size
    r = rl.Roofline(a.arch, a.shape, "x".join(map(str, mesh.devices.shape)),
                    n, cost[0] * n, cost[1] * n, cost[2] * n, c2[3],
                    mesh_mod.model_flops(cfg, a.shape))
    print(f"\n=== {a.arch} x {a.shape} "
          f"overrides={a.set} rules={a.rule} ({time.monotonic()-t0:.0f}s) ===")
    print(f"t_compute={r.t_compute*1e3:.2f}ms t_memory={r.t_memory*1e3:.2f}ms "
          f"t_collective={r.t_collective*1e3:.2f}ms bound={r.bottleneck}")
    print(f"useful={r.useful_flop_frac:.3f} roofline_frac={r.roofline_frac:.5f}")
    print("collectives/dev: " + ", ".join(
        f"{k}={v/1e9:.2f}GB" for k, v in c2[3].items() if v))
    if a.profile:
        profile_hlo(c2c.as_text())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dse", action="store_true",
                   help="hillclimb the edge-DSE design space instead")
    p.add_argument("--system", action="store_true",
                   help="hillclimb the multi-stream SYSTEM plane (one "
                        "accelerator time-shared by --stream bundles)")
    p.add_argument("--stream", action="append", default=[],
                   metavar="WORKLOAD=IPS",
                   help="[system] stream spec (repeatable; default: the "
                        "paper XR bundle detnet=10, edsnet=0.1)")
    p.add_argument("--workload", default="detnet",
                   help="[dse] workload / config name")
    p.add_argument("--objective", default="edp",
                   choices=("edp", "energy", "pmem"))
    p.add_argument("--ips", type=float, default=10.0,
                   help="[dse] inference rate for the pmem objective")
    p.add_argument("--arch", help="[roofline] LM config name")
    p.add_argument("--shape", help="[roofline] decode/prefill shape")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--set", action="append", default=[],
                   help="cfg field override, e.g. swa_ring_buffer=True")
    p.add_argument("--rule", action="append", default=[],
                   help="sharding rule override, e.g. expert_cap=pod,data")
    p.add_argument("--profile", action="store_true")
    a = p.parse_args()
    if a.system:
        system_main(a)
    elif a.dse:
        dse_main(a)
    else:
        if not (a.arch and a.shape):
            p.error("roofline mode needs --arch and --shape (or use --dse)")
        roofline_main(a)


if __name__ == "__main__":
    main()
