"""Share of the window in which no operation ran on the device."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device.busy_s(0.0, run.window_s) / run.window_s)
