"""Mean PQ distance evaluations per query (``SearchResult.n_dist``) over
the traced calls: the beam's work, which sets the batch cell's qps."""

import numpy as np


def read(ctx):
    if not ctx.records:
        return None
    return float(np.mean(np.concatenate([r.n_dist for r in ctx.records])))
