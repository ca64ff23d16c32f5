"""Share of the traced rounds in which no operation ran on the device."""


def read(ctx):
    red = ctx["red"]
    if not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
