"""Share of the traced window in which the fs4 ``hop_adc_fs`` kernel ran
on the device. No kernel event in the trace: nothing to read."""


def read(ctx):
    k = ctx.kernel("hop_adc_fs")
    if not k.events:
        return None
    return 100.0 * sum(d for _, _, d in k.events) / 1e9 / ctx.trace.window_s
