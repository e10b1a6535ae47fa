"""ResNet forward and backward operations (three times the forward's,
from shapes) of every image the window's rounds trained on, over the
window's wall clock, over the chip's bf16 peak (float32 convolutions at
default precision take one bf16 pass)."""


def read(ctx):
    c = ctx.counters
    if "images" not in c or c.get("wall_s", 0) <= 0:
        return None
    ops = c["train_ops_per_image"] * c["images"]
    return 100.0 * ops / c["wall_s"] / ctx.peak["bf16_flops"]
