"""Share of the traced window in which no operation ran on the device,
in percent, in the closed-loop cells."""


def read(rec):
    tr = rec.get("trace")
    if rec.get("loop") != "closed" or not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
