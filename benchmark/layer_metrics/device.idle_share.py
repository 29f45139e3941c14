"""Share of the traced window in which no operation ran on the device:
1 - union of the device operations' intervals over the window. The gap a
client sees between two tokens is a dispatch's device time plus the idle
time before the next dispatch, which is how this moves `itl_mean_ms`."""
UNIT = "%"
LAYER = "device"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    t = ctx.trace
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
