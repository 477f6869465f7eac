"""The fused coded-matmul kernel's share of its roofline.

Per call and chip the work is counted from the plan and the shapes, not
from the kernel's tiling: 2 C bm k n operations for the C coded blocks on
the chip, and A, x and the coded output each moved once.  The least time
of a call is the larger of operations over the bf16 peak and bytes over
the HBM bandwidth; the share is that least time, times the calls, over the
summed device time of the kernel's events (``coded_matmul``) on the cell's
chips.  Moves ``products_per_s``."""

KERNEL = "coded_matmul"


def read(ctx):
    flops = ctx.work["kernel_flops_per_chip"]
    nbytes = ctx.work["kernel_bytes_per_chip"]
    t_flops = flops / ctx.peaks["bf16_flops"]
    t_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    calls = total_ns = 0
    for dev in ctx.devices:
        n, ns = ctx.trace.ops(KERNEL, dev)
        calls += n
        total_ns += ns
    if calls == 0 or total_ns == 0:
        return None
    bound = "compute" if t_flops >= t_bytes else "memory"
    ctx.log(f"{KERNEL}: {calls} kernel events, {total_ns / 1e9:.6f} s, "
            f"{bound}-bound (least time {max(t_flops, t_bytes) * 1e3:.6f} ms"
            f" per call, mean {total_ns / calls / 1e6:.6f} ms)")
    return 100.0 * calls * max(t_flops, t_bytes) / (total_ns / 1e9)
