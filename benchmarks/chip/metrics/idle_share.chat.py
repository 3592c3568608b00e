"""Percent of the traced window in which no op ran on the device
(averaged over the chips), in a serving cell."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["chips"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
