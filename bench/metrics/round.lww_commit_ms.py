"""Device time per round of the chronological last-write-wins commit: the
summed durations, in the traced window, of the device operations whose
op name carries the program's ``lww_commit`` scope (the memory and mail
commits' scatters, and the pad and slice that add and drop the
committer's scratch row; copies XLA inserts on its own carry no op name
and are not counted), over the rounds dispatched in it. Nothing where
the program names no such scope."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("window_rounds"):
        return None
    s = sum(v for name, v in tr.get("ops", {}).items()
            if "lww_commit" in name)
    return s / rec["window_rounds"] * 1e3 if s else None
