"""train.idle_launch_share: the share of the traced stretch, in %, in which no
op ran on the device while the trainer loop was in ``train.fetch`` before
the step's first device op: the step's inputs in flight, or the step not
launched (``programspans``)."""
import programspans


def read(ctx):
    return programspans.share(ctx, "launch")
