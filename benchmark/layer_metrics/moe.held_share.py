"""Share of the routers' assignments that reached an expert this chip holds:
`batch_moe_assignments_total` (assignments on held experts, counted on the
device) over `batch_moe_routed_total` (dispatched rows x experts per token x
routed layers: every assignment the routers made), both of
`runtime/batch_engine.py`. 100 where the checkpoint holds every expert the
router scores; 25 where it holds 48 of 192 and routing is even: what this
chip's share of the expert load was in the window. A program without the
second counter (the parent of the PR that added it) reads nothing."""
UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    held = ctx.counter_delta("batch_moe_assignments_total")
    routed = ctx.counter_delta("batch_moe_routed_total")
    if held is None or not routed:
        print("moe.held_share: the program counts no routed assignments",
              flush=True)
        return None
    return 100.0 * held / routed
