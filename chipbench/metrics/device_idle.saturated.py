"""Device: share of the traced window in which no operation ran on the
chip (one minus the busy union over the window)."""


def read(run):
    tr = run.reduced
    if tr is None or not tr.chips:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
