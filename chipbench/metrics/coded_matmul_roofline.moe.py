"""The grouped coded-matmul kernel's share of its roofline in the coded
expert layer: both grouped calls' least time, from each request's real
columns (routed and shared; padding and dead tiles do no useful work),
over the summed device time of the kernel's events (``coded_matmul``).

One call with n real columns, E matrices of ``rows`` x k under a code of C
coded blocks of bm rows: 2 C bm k n operations; the E matrices, the input
(k x n) and the coded output (C bm x n) each moved once.  Its least time
is the larger of operations over the bf16 peak and bytes over the HBM
bandwidth.  Moves ``products_per_s``."""

KERNEL = "coded_matmul"


def flops(p: dict, n: int) -> float:
    return 2.0 * p["C"] * p["bm"] * p["k"] * n


def nbytes(p: dict, n: int) -> float:
    return p["itemsize"] * (p["E"] * p["rows"] * p["k"] + p["k"] * n
                            + p["C"] * p["bm"] * n)


def read(ctx):
    peak, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    least = {"compute": 0.0, "memory": 0.0}
    for n in ctx.work["real_columns"]:
        for p in ctx.work["kernel_products"]:
            t_f, t_b = flops(p, n) / peak, nbytes(p, n) / bw
            least["compute" if t_f >= t_b else "memory"] += max(t_f, t_b)
    calls = total_ns = 0
    for dev in ctx.devices:
        n, ns = ctx.trace.ops(KERNEL, dev)
        calls += n
        total_ns += ns
    if calls == 0 or total_ns == 0:
        return None
    ctx.log(f"{KERNEL} (grouped): {calls} kernel events, "
            f"{total_ns / 1e9:.6f} s; least time {least['compute']:.6f} s "
            f"compute-bound, {least['memory']:.6f} s memory-bound")
    return 100.0 * sum(least.values()) / (total_ns / 1e9)
