"""Device-idle time in the traced window during which the engine's thread
was in the driver loop around the steps (innermost span a ``driver_*``
span other than ``driver_idle``: its lock, calls, offers, token fan-out),
over the window."""

from harness import idle


def read(run):
    s = idle.shares(run)
    return None if s is None else s["driver"]
