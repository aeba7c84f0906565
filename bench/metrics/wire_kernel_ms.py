"""Device time of the wire-stage Pallas kernels per round, averaged over
the chips the cell uses (ms)."""


def read(ctx):
    chips = ctx["trace"].get("chips", [])
    if not chips or not all(c["kernel_events"] for c in chips):
        return None
    return 1e3 * sum(c["kernel_s"] for c in chips) / len(chips) / ctx["rounds"]
