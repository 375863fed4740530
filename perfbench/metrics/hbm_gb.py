"""Device: bytes one chip holds for the timed step, from the compiler's
``memory_analysis()`` of that step: arguments + temporaries + outputs -
outputs aliased to donated arguments."""


def read(ctx):
    ma = ctx["memory_analysis"]
    if ma is None:
        return None
    return (ma["argument"] + ma["temp"] + ma["output"] - ma["alias"]) / 1e9
