"""Device time of the round's metrics per round, averaged over the chips
the cell uses (ms): every busy instant whose innermost operation runs in
the program's ``fl_metrics`` scope (loss, gradient norm, consensus error
and the wire state's metrics; on several chips their all-reduces)."""

from stages import scope_ms_per_round

SCOPE = "fl_metrics"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
