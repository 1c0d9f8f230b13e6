"""vlm.train_mfu: the VLM training step's share of the chip's bf16 peak, in %.

Operations per frame (``flops_vlm.train_flops``: 6 x the forward's
multiply-adds, the patch embedding's input gradient left out, the routed
experts counted for the work done on this chip) times the untraced window's
frames per second, over the peak of ``peaks.json``."""
import flops_vlm


def read(ctx):
    out = ctx["outcome"]
    rate = out.info["images"] / out.window.seconds
    flops = flops_vlm.train_flops(ctx["cfg"], ctx["traffic"]["seq_len"])
    return 100.0 * flops * rate / ctx["peak"]["bf16_flops_per_s"]
