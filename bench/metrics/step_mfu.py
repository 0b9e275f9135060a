"""The whole step's share of the chip's peak, in percent: the operations
the model requires for the events applied in the window
(``counts.event_flops`` per lane, native widths) per second of the
window, over the chips' peak bfloat16 FLOP/s."""


def read(rec):
    c = rec.get("counts") or {}
    if rec.get("loop") != "closed" or not c.get("step_flops"):
        return None
    p = rec["peaks"]
    return (100.0 * c["step_flops"] / rec["window_s"]
            / (rec["chips"] * p["bf16_flops_per_s"]))
