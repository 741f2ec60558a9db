"""Share of its HBM roofline that the u8 ``hop_adc`` kernel reaches:
(calls x least time of one call) over the calls' summed device time. The
least time is the algorithmic bytes of a call (``costs/hop_adc.py``) over
peak HBM bandwidth: its Q·R'·M lookup-adds are far below any compute
peak. No kernel event in the trace: nothing to read."""


def read(ctx):
    k = ctx.kernel("hop_adc")
    if not k.events:
        return None
    least = k.bytes / ctx.peaks["hbm_bytes_per_s"]
    spent = sum(d for _, _, d in k.events) / 1e9
    return 100.0 * len(k.events) * least / spent
