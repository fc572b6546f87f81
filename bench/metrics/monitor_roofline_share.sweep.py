"""The Pallas monitor kernel's share of its roofline in the sweep: the
least time of a sliding single-bin DFT monitor over the real samples of
the rows the window dispatched (``costs.monitor_least_work``, at the
chip's published peaks) over the kernel's device time in the trace."""
import costs

#: the Mosaic monitor's custom call in the trace, named after the jitted
#: function around it (``_sliding_monitor_full``, vmapped)
KERNEL = r"^vmap_jit__sliding_monitor_full__"


def read(ctx):
    mon = ctx.stats.get("monitor")
    t = ctx.kernel_s("_mitigate_vmapped", KERNEL)
    if not mon or not t:
        return None
    rows = [int(length) for length, n in ctx.stats["rows_by_length"].items()
            for _ in range(n)]
    ops, nbytes = costs.monitor_least_work(rows, mon["bins"])
    share, bound = costs.roofline(ops, nbytes, t, ctx.peak)
    ctx.note("monitor_roofline_share.sweep", {
        "bound": bound, "ops": ops, "bytes": nbytes, "kernel_s": t})
    return share
