"""device_idle_share: 1 minus the union of the intervals in which an operation
ran on the device, over the traced window, in % (mean over the chips)."""


def read(record):
    tr = record["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
