"""Share of the traced window in which no operation ran on the device,
in %.  Layer: device."""

from metrics.work import share


def read(rec):
    s = rec["summary"]
    return None if s is None else share(s.window_s - s.busy_s, s.window_s)
