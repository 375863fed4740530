"""Update: device milliseconds per step in which a collective runs and no
other op does, averaged over the chips (the part of the exchange that
nothing hides), over the steps of the traced window."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["collective_ops"] == 0:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
