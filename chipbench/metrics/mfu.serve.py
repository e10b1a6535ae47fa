"""Model operations of the useful tokens of the window (the admitted
prompts' forward passes and every decoded token's, 2 x matmul parameters
per token plus attention over its context), over the window's wall clock,
over the chip's int8 peak."""


def read(ctx):
    c = ctx.counters
    if "model_ops" not in c or c.get("wall_s", 0) <= 0:
        return None
    return 100.0 * c["model_ops"] / c["wall_s"] / ctx.peak["int8_ops"]
