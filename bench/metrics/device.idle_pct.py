"""Share of the traced stretch in which no operation ran on the device."""


def read(records):
    tr = records["trace"]
    if not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
