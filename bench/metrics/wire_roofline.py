"""The wire stage's share of its roofline (percent): the least time one
chip's HBM needs for the bytes the stage must read and write per round,
over the stage's kernel time per round on that chip. The stage is bound
by memory: it does a few operations per byte."""


def read(ctx):
    chips = ctx["trace"].get("chips", [])
    if not chips or not all(c["kernel_events"] for c in chips):
        return None
    kernel_s = sum(c["kernel_s"] for c in chips) / len(chips) / ctx["rounds"]
    least_s = ctx["wire_bytes_per_chip"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
