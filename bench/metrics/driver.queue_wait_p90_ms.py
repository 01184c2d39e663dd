"""90th percentile, over the requests due in the window, of the time each
waited between the driver taking it (``DriverHandle.t_submit``) and the
engine admitting it to a slot (``t_admit``), in ms."""

from harness.session import percentile


def read(run):
    waits = [r.t_admit - r.t_submit for r in run.in_window if r.t_admit]
    return percentile(waits, 90) * 1e3 if waits else None
