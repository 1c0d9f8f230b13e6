"""vlm.moe_share: the share of the device's busy time in the traced stretch
spent in the MoE layers' router, routed experts and shared experts (scopes ``moe.router``, ``moe.routed``, ``moe.shared``), forward, backward and
rematerialised forward, in % (``vlmtrace``)."""
import vlmtrace


def read(ctx):
    return vlmtrace.group_share(ctx, "moe")
