"""Median host time of one ``SessionManager.step`` in the window
(``mgr.metrics[*]["latency_s"]``): staging and dispatch of a round,
which runs while the device works on the round before."""
import statistics


def read(rec):
    if rec.get("loop") != "closed" or not rec.get("host_step_s"):
        return None
    return statistics.median(rec["host_step_s"]) * 1e3
