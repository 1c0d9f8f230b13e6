"""train.idle_input_share: the share of the traced stretch, in %, in which no
op ran on the device while the trainer loop was in ``train.next`` or
``train.put``: the loader, or the batch's copy to the device
(``programspans``)."""
import programspans


def read(ctx):
    return programspans.share(ctx, "input")
