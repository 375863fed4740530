"""Device: the share of the traced window in which no op runs, averaged
over the chips: 1 - union of op intervals / window."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["devices"] == 0 or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
