"""Encoder FLOPs on the real tokens encoded in the window, over the
window, over the chip's bf16 peak."""

from tpubench import work


def read(r):
    seconds = r.out["window_s"]
    if seconds <= 0:
        return None
    flops = work.encoder_flops(r.enc, r.out["encoded_tokens"])
    return r.share(flops / r.peak["bf16_flops_per_s"], seconds)
