"""Share of the traced window in which no program ran on the device, in
percent: 1 - busy / window."""


def read(facts, red, peaks):
    if red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
