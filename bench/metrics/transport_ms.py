"""Device time of the exchange between chips per round, averaged over the
chips the cell uses (ms): every busy instant whose innermost operation runs
in the program's ``fl_transport`` scope (the ppermutes of the int8
payload and its scales)."""

from stages import scope_ms_per_round

SCOPE = "fl_transport"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
