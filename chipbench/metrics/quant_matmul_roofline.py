"""``quant_matmul``'s share of its roofline: the least time every packed
projection of the window's step calls could take (the larger of its
2*M*K*N operations over the chip's int8 peak and its bytes over HBM
bandwidth, summed over the calls), over the summed ``quant_matmul`` kernel
time in the trace.  The int8 peak is the ceiling because the weights are
int8 codes."""


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if t is None or "quant_matmul_rows" not in c:
        return None
    busy = t.kernel_s("quant_matmul")
    if busy <= 0:
        return None
    least = 0.0
    for rows in c["quant_matmul_rows"]:
        for ops, byt, n in ctx.mod.quant_matmul_calls(ctx.cfg, rows):
            least += n * max(ops / ctx.peak["int8_ops"],
                             byt / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
