"""Share of the traced slice in which no op ran on the chip (worst chip):
1 - union of the device's op intervals / traced window. Every traced run
also reports `device.busy_s` and `device.window_s`, from which the driver
works out the same share for every cell; as a per-layer metric it is read
where the serial decode tick (ROADMAP S4) would show."""
NAME, UNIT = "device_idle_share", "%"
LAYER, MOVES, SOURCE = "device", "tpot_p50_s", "device_trace"


def read(ctx):
    if not ctx.get("trace"):
        return None
    return 100.0 * ctx["trace"]["idle_share"]
