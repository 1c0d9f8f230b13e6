"""vlm.vision_share: the share of the device's busy time in the traced stretch
spent in the vision tower and the projector (scopes ``vision``, ``projector``), forward, backward and
rematerialised forward, in % (``vlmtrace``)."""
import vlmtrace


def read(ctx):
    return vlmtrace.group_share(ctx, "vision")
