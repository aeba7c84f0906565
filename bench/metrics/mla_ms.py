"""Device time of the latent attention per round, averaged over the chips
the cell uses (ms): every busy instant whose innermost operation runs in
the program's ``mla`` scope (each layer's attention, its backward and its
recomputation, in every local step)."""

from stages import scope_ms_per_round

SCOPE = "mla"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
