"""RPQ training's rate in set-up: ``fit``'s steps over the wall time of
its set-up phase ``rpq_fit`` (host clock), the feature samplers and each
refresh of the routing beams included. A quantizer not trained by ``fit``:
nothing to read."""


def read(ctx):
    if "rpq_fit" not in ctx.phases:
        return None
    return ctx.conf["quantizer"]["fit"]["steps"] / ctx.phases["rpq_fit"]
