"""The fused step kernel's share of its roofline, in percent: the least
time the chip could take for the kernel's required work (the larger of
its operations over peak bfloat16 FLOP/s and its bytes over HBM
bandwidth, from ``counts.fused_row_*`` at native widths for the valid
rows of the traced window), over the kernel's measured device time.
``fused_bound`` in the record says which of the two bounds it."""


def read(rec):
    tr = rec.get("trace")
    c = rec.get("counts") or {}
    if not tr or not tr.get("fused_s") or not c.get("fused_rows"):
        return None
    p = rec["peaks"]
    t = max(c["fused_flops"] / p["bf16_flops_per_s"],
            c["fused_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * t / tr["fused_s"]
