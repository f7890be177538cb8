"""Share (%) of the traced window in which no operation ran on the device:
one minus the union of the device operations' intervals over the window,
averaged over the chips used.  Nothing to read where the trace holds no
device operation."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["device"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / (tr["hi"] - tr["lo"]))
