"""train.idle_dispatch_share: the share of the traced stretch, in %, in which
no op ran on the device while the trainer loop was in ``train.dispatch``:
the call of the jitted step (``programspans``)."""
import programspans


def read(ctx):
    return programspans.share(ctx, "dispatch")
