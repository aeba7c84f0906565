"""Model FLOP/s utilization of the whole round (percent): the forward and
backward FLOPs of every site's local steps, from shapes with nothing
recomputed, over the traced window times the chips times their peak."""


def read(ctx):
    tr = ctx["trace"]
    if "chips" not in tr:
        return None
    done = ctx["flops_per_round"] * ctx["rounds"]
    return 100.0 * done / (tr["window_s"] * ctx["chips"]
                           * ctx["peaks"]["flops_per_s"])
