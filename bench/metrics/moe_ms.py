"""Device time of the expert layers per round, averaged over the chips the
cell uses (ms): every busy instant whose innermost operation runs in the
program's ``moe`` scope (router, dispatch, the held experts' grouped
products, the shared expert and the combine, with their backward and
recomputation, in every local step)."""

from stages import scope_ms_per_round

SCOPE = "moe"


def read(ctx):
    return scope_ms_per_round(ctx, SCOPE)
