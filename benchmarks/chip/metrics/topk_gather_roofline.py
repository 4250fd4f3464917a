"""Kernel ``topk_gather``: the least time its calls in the traced
stretch could take at the chip's peaks (counted work, see
``chipbench.counts.topk_gather``), over the device time they took."""

from chipbench import counts as CN

KERNEL = "topk_gather"


def read(ctx):
    if not ctx.trace:
        return None
    calls, seconds = ctx.trace.kernel(KERNEL)
    if not calls or seconds <= 0:
        return None
    m, d = ctx.record.data["model"], ctx.record.data
    sp = m["ffn_sparsity"]
    n = sp["n"]
    g, p = m["d_model"] // n, m["d_ff"] // n
    route_groups = 1 if sp["route_share"] == 0 else g // sp["route_share"]
    work = CN.topk_gather(
        b=d["n_slots"], k=CN.k_for(m["d_ff"], sp["k_frac"]), p=p, g=g, n=n,
        route_groups=route_groups,
        weight_bytes=CN.DTYPE_BYTES[m["param_dtype"]], route_bytes=1,
        value_bytes=CN.DTYPE_BYTES[m["compute_dtype"]], index_bytes=4,
        out_bytes=CN.DTYPE_BYTES[m["compute_dtype"]])
    least = work.seconds_at(ctx.peaks["flops_per_s"], ctx.peaks["bytes_per_s"])
    return 100.0 * calls * least / seconds
