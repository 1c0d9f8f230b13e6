"""vlm.routed_gmm_roofline: the routed experts' grouped matmuls against their
roofline, in %: the least time the chip could take for the products of the
token-slots the router state counted over the traced steps
(``flops_vlm.routed_gmm_least_seconds``, peaks of ``peaks.json``), over the
device time of the step's grouped-matmul ops in the traced window
(``vlmtrace``). The yardstick reads the same work whatever implements it:
rows a kernel pads, and rematerialised products, show as lost share."""
import flops_vlm
import vlmtrace


def read(ctx):
    info = ctx["outcome"].info
    sec = vlmtrace.seconds(ctx)
    slots = info.get("traced_routed_slots")
    if not sec or not sec.get("gmm") or not slots:
        return None
    cfg = ctx["cfg"]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least = flops_vlm.routed_gmm_least_seconds(
        cfg, slots, moe_layers * info["traced_steps"], ctx["peak"])
    return 100.0 * least / sec["gmm"]
