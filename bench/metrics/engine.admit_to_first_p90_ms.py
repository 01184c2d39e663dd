"""90th percentile, over the requests due in the window that have a
``frontend_queued`` span, of their ``prefill`` span: from admission to a
slot to the first token (chunked prefill interleaved with decode), in
ms."""

from harness.session import percentile


def read(run):
    queued = {e.track[1] for e in run.spans
              if e.name == "frontend_queued" and e.track[0] == "requests"}
    prefill = {e.track[1]: e.dur for e in run.spans
               if e.name == "prefill" and e.track[0] == "requests"}
    waits = [prefill[r.uid] for r in run.in_window
             if r.uid in queued and r.uid in prefill]
    return percentile(waits, 90) * 1e3 if waits else None
