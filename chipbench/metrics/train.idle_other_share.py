"""train.idle_other_share: the share of the traced stretch, in %, in which no
op ran on the device while the trainer loop was in ``train.hooks``, a bare
``train.step``, or no program span (``programspans``)."""
import programspans


def read(ctx):
    return programspans.share(ctx, "other")
