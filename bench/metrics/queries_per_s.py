"""Queries of the window's completed, served requests over the time from
the first request's start to the last one's end (host clock).  A request
completes when its answers are on the host; a failed one serves nothing."""


def read(run):
    served = sum(r.n for r in run.requests if not r.failed)
    return served / run.window_s
