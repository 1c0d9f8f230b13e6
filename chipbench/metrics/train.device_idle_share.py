"""train.device_idle_share: the share of the traced stretch of training in
which no operation ran on the device, in %."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
