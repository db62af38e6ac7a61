"""Share of the traced solve in which no op ran on the device, averaged over
the chips: 100 * (1 - busy union / window) (layer: device)."""


def read(r):
    if not r.ops:
        return None
    return 100.0 * (1.0 - r.busy_s() / r.window_s())
