"""``flash_attention``'s share of its roofline: for every prefill call of
the window, the larger of its causal attention operations over the bf16
peak and its q, k, v and output bytes over HBM bandwidth, summed, over the
summed ``flash_attention`` kernel time in the trace."""


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if t is None or "flash_attention" not in c:
        return None
    busy = t.kernel_s("flash_attention")
    if busy <= 0:
        return None
    least = sum(max(ops / ctx.peak["bf16_flops"],
                    byt / ctx.peak["hbm_bytes_per_s"])
                for ops, byt in c["flash_attention"])
    return 100.0 * least / busy
