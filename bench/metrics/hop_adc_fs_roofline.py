"""Share of its HBM roofline that the fs4 ``hop_adc_fs`` kernel reaches
(``costs/hop_adc_fs.py``; the formula is ``harness.layer_context``'s
``roofline``)."""


def read(ctx):
    return ctx.roofline("hop_adc_fs")
