"""Share of the device's busy time that the latent attention kernel takes: the
device time of the operations named `latent_paged_attention*`
(`ops/pallas_paged_attention.py`) over the union of all operations' intervals
in the window. It says that the kernel engages (a program that reads the
latent rows through XLA's gather reads nothing here) and how much of a step
reading the cache is: in a decode-heavy cell at positions under 768 the
weights are most of a step and this stays small; a long-context cell is where
it would grow. A program without the kernel reads nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
MARK = "latent_paged_attention"


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    seconds = moe_trace.seconds(planes, MARK)
    if seconds == 0.0:
        print("step.latent_attn_share: no operation of the window is the "
              "latent attention kernel", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    print(f"step.latent_attn_share: latent attention {seconds:.3f} s of "
          f"{busy:.3f} s busy", flush=True)
    return 100.0 * seconds / busy
