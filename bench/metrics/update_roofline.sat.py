"""Share of the update kernel's device time that the requests' own work
needs at the chip's peaks: the larger of (their operands' and results'
bytes) / HBM bandwidth and (their GGR flops) / peak flop rate, over the
kernel's time in the traced window, in percent."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_done or ctx.trace["kernel_s"] <= 0:
        return None
    if ctx.peaks is None:
        raise KeyError("no peaks for this device in bench/peaks.json")
    t_mem = ctx.work["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    t_flop = ctx.work["flops"] / ctx.peaks["bf16_flops_per_s"]
    ctx.log(f"update_roofline: bound by {'HBM' if t_mem >= t_flop else 'flops'} "
            f"({t_mem!r} s vs {t_flop!r} s per request)")
    return 100.0 * ctx.traced_done * max(t_mem, t_flop) / ctx.trace["kernel_s"]
