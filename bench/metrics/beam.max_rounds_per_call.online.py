"""Beam rounds of a call's slowest lane (lanes run in lockstep, so the
call lasts that many rounds), averaged over the traced calls: what sets
one caller's latency."""

import numpy as np


def read(ctx):
    if not ctx.records:
        return None
    return float(np.mean([np.max(r.rounds) for r in ctx.records]))
