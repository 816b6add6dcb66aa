"""Scheduler (datapath/scheduler.py): scans per cross-request bucket pass,
the window's change in the Pod's `xreq_requests` over its change in
`xreq_groups`.  A group of one counts as a pass too, so 1 means nothing
was stacked."""


def read(r):
    d = r.window
    return d["xreq_requests"] / d["xreq_groups"] if d["xreq_groups"] else None
