"""``flash_decode``'s share of its roofline: for every decode call of the
window, the larger of its attention operations over the bf16 peak and the
K/V page bytes each live slot reads (float32 pool) over HBM bandwidth,
summed, over the summed ``flash_decode`` kernel time in the trace."""


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if t is None or "flash_decode" not in c:
        return None
    busy = t.kernel_s("flash_decode")
    if busy <= 0:
        return None
    least = sum(max(ops / ctx.peak["bf16_flops"],
                    byt / ctx.peak["hbm_bytes_per_s"])
                for ops, byt in c["flash_decode"])
    return 100.0 * least / busy
