"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device op intervals."""


def read(rec):
    red = rec["trace"]
    if red is None or not red.devices:
        return None
    return 100.0 * (1.0 - red.busy_s / rec["window"]["elapsed_s"])
