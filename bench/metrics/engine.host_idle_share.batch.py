"""Device-idle time in the traced window during which the engine's thread
was in a step's host code (innermost span ``step``, an engine phase or a
``compile``), over the window."""

from harness import idle


def read(run):
    s = idle.shares(run)
    return None if s is None else s["engine"]
