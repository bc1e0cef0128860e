"""Device idle share of the traced window: 1 minus the union of the
chip's operation intervals over the window, in percent."""


def read(run):
    if run.trace is None or not run.trace["device"]:
        return None
    return run.trace["idle_share"] * 100
