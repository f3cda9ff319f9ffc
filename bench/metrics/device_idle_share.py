"""Share of the traced window in which no operation ran on the device, in %.

1 - (union of the device's op intervals inside the window) / (the traced
window's length), both on the trace's clock, averaged over the chips used.
"""


def read(m):
    if m.window_s <= 0:
        return None
    return 100.0 * (1.0 - m.trace["busy_s"] / m.window_s)
