"""Share of its HBM roofline that the u8 ``hop_adc`` kernel reaches
(``costs/hop_adc.py``; the formula is ``harness.layer_context``'s
``roofline``)."""


def read(ctx):
    return ctx.roofline("hop_adc")
