"""train.idle_fetch_share: the share of the traced stretch, in %, in which no
op ran on the device while the trainer loop was in ``train.fetch`` from the
step's first device op on: gaps inside the step, the loss's return
(``programspans``)."""
import programspans


def read(ctx):
    return programspans.share(ctx, "fetch")
