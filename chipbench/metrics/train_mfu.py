"""train_mfu: the training step's share of the chip's bf16 peak, in %.

Operations per image (``flops.train_flops``: 6 x MACs, less the first
layer's input gradient) times the images of the untraced window over its
length, over the peak of ``peaks.json``. The rate is the one
``train_images_per_s`` reports: the profiler slows the host's side of a
step, so the traced stretch is not used here."""
import flops


def read(ctx):
    out = ctx["outcome"]
    rate = out.info["images"] / out.window.seconds
    return 100.0 * flops.train_flops(ctx["cfg"]) * rate / ctx["peak"]["bf16_flops_per_s"]
