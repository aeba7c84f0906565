"""Device time of the round's wire stage per round, averaged over the chips
the cell uses (ms): every busy instant whose innermost operation runs in
the program's ``fl_wire`` scope (the wire kernel, the casts and gathers
around it, and on several chips the ``fl_transport`` inside it)."""

from stages import scope_ms_per_round

SCOPE = "fl_wire"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
