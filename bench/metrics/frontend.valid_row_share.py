"""Valid rows over the rows the window's rounds computed (every lane slot
of the coalesced round times the padded width), in percent: the share of
the device's work that deadline batching and padding leave useful."""


def read(rec):
    if rec.get("loop") != "open" or not rec.get("dispatched_rows"):
        return None
    return 100.0 * rec["valid_rows"] / rec["dispatched_rows"]
