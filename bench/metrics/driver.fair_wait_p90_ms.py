"""90th percentile, over the requests due in the window that have a
``frontend_queued`` span, of its length: the wait in the frontend's fair
queue, from the driver taking the request to offering it to the engine,
in ms."""

from harness.session import percentile


def read(run):
    queued = {e.track[1]: e.dur for e in run.spans
              if e.name == "frontend_queued" and e.track[0] == "requests"}
    waits = [queued[r.uid] for r in run.in_window if r.uid in queued]
    return percentile(waits, 90) * 1e3 if waits else None
