"""Share of the window in which no program ran on the device: 1 minus the
union of the program intervals over the window, averaged over devices."""


def read(w):
    return w.device.idle_pct if w.device is not None else None
