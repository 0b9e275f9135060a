"""Device busy time per round: the union of device-operation intervals in
the traced window, over the rounds dispatched in it."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("window_rounds"):
        return None
    return tr["busy_s"] / rec["window_rounds"] * 1e3
