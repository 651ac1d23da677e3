"""Share of the measured window in which no operation ran on the device
(union of the trace's device op intervals), in percent."""


def read(win):
    if win.trace is None or win.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - win.trace.busy_s / win.trace.window_s)
