"""Share of the traced window in which no operation ran on the chip:
100 x (1 - busy / window) on the busiest traced chip."""


def read(run):
    tr = run.device_trace
    if tr is None or tr.window_s <= 0 or tr.busiest is None:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.busiest) / tr.window_s)
