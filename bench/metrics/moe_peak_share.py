"""The expert layers' share of the chip's bf16 peak (percent): their
forward and backward FLOPs a round on one chip, with nothing recomputed,
over the ``moe`` scope's device time a round times the peak. The router and
the shared SwiGLU count from shapes; the held experts from the round's
``moe_held_assignments`` counter (the comm step's assignments to the held
experts, summed over the expert layers, the mean over sites) times Q.

The shapes are those of the one cell that reads it, ``CELL``. Nothing
where the program reports no such counter or no ``moe`` scope."""

import harness
from stages import scope_ms_per_round

SCOPE = "moe"
CELL = "kanana30b-ring4-4chip-dsgd-q4"


def read(ctx):
    held = ctx["round_metrics"].get("moe_held_assignments")
    ms = scope_ms_per_round(ctx, SCOPE)
    if held is None or not ms:
        return None
    cell = harness.resolve(CELL)
    t = cell.traffic
    tokens = int(t["batch"]) * int(t["seq_len"])
    sites = int(t["graph"]["n"]) // ctx["chips"]
    flops = (cell.model.expert_layer_flops(cell.config, tokens, held)
             * int(t["q"]) * sites)
    return 100.0 * flops / (ms * 1e-3 * ctx["peaks"]["flops_per_s"])
