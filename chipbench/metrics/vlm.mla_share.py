"""vlm.mla_share: the share of the device's busy time in the traced stretch
spent in latent attention (scope ``mla``), in the dense layer and the MoE layers, forward, backward and
rematerialised forward, in % (``vlmtrace``)."""
import vlmtrace


def read(ctx):
    return vlmtrace.group_share(ctx, "mla")
