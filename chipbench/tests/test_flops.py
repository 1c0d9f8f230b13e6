"""The FLOP/byte table against the program's own layer plan, as it stands.

``flops.layer_table`` walks the benchmark's copy of the architecture. These
tests tie it to ``xr.conv_layer_specs``, the plan the program trains and the
pricing plane prices: a later change to the plan fails here instead of
moving the numerator of ``train_mfu`` unseen."""
import json
import os

import pytest

import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Forward MACs per image of the full configurations, from the plan as it
# stands: DetNet 0.098 G, EDSNet 10.39 G.
MACS = {"detnet": 98_040_320, "edsnet": 10_392_913_920}


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        return json.load(fh)


def program_specs(cfg):
    from harness import Run
    from repro.models import xr
    pcfg = Run(cell="t", cfg=cfg, traffic={}, limits={}, seed=0, seconds=0,
               trace=False, t_start=0.0, counter=None).program_config()
    return xr.conv_layer_specs(pcfg)


@pytest.mark.parametrize("name", ["detnet", "edsnet"])
def test_table_matches_program_plan(name):
    cfg = config(name)
    rows, specs = flops.layer_table(cfg), program_specs(cfg)
    assert [r["name"] for r in rows] == [s.name for s in specs]
    for r, s in zip(rows, specs):
        assert r["op"] == s.kind, s.name
        assert r["in_hwc"][2] == s.in_ch and r["out_hwc"][2] == s.out_ch, s.name
        if s.kind != "dense":
            assert r["k"] == s.kernel and r["stride"] == s.stride, s.name
            assert tuple(r["in_hwc"][:2]) == tuple(s.in_hw), s.name
        assert r["macs"] == s.macs, s.name


@pytest.mark.parametrize("name", ["detnet", "edsnet"])
def test_totals(name):
    cfg = config(name)
    macs = sum(r["macs"] for r in flops.layer_table(cfg))
    assert macs == MACS[name]
    assert flops.forward_flops(cfg) == 2 * macs
    stem = flops.layer_table(cfg)[0]
    assert stem["name"] == "stem"
    assert flops.train_flops(cfg) == 6 * macs - 2 * stem["macs"]


def test_conv_least_time_bounds():
    cfg = config("edsnet")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    fwd = flops.conv_least_seconds(cfg, 16, peak, train=False)
    train = flops.conv_least_seconds(cfg, 16, peak, train=True)
    conv_flops = 16 * sum(r["fwd_flops"] for r in flops.layer_table(cfg)
                          if r["op"] != "dense")
    assert fwd >= conv_flops / peak["bf16_flops_per_s"]
    assert 2 * fwd < train <= 3 * fwd
