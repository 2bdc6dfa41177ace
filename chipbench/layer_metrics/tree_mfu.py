"""The whole round's share of the chip's peak: the least time the chip could
take for the trees of the window, over the window."""


def read(ctx, spec):
    win = ctx["window"]
    if not ctx["least_s"] or win["seconds"] <= 0:
        return None
    return 100.0 * sum(ctx["least_s"]) / win["seconds"]
