"""Share of the traced window in which no operation ran on the chip: the
time the host (sweep loop, shard commits, copies) keeps the device
waiting."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
