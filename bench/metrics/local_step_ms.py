"""Device time of the round's local steps per round, averaged over the chips
the cell uses (ms): every busy instant whose innermost operation runs in
the program's ``fl_local`` scope (the Q-1 step scan and the comm step's
forward and backward, with the flat state's conversion)."""

from stages import scope_ms_per_round

SCOPE = "fl_local"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
