"""Device time of the fused step kernel per round: the summed durations
of its events in the traced window, over the rounds dispatched in it."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("window_rounds") or not tr.get("fused_s"):
        return None
    return tr["fused_s"] / rec["window_rounds"] * 1e3
