"""Share of the measured window in which no operation runs on the chip,
averaged over the chips the cell uses (percent)."""


def read(ctx):
    tr = ctx["trace"]
    if "chips" not in tr:
        return None
    busy = sum(c["busy_s"] for c in tr["chips"]) / len(tr["chips"])
    return 100.0 * (1.0 - busy / tr["window_s"])
