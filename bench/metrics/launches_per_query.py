"""Kernel API (kernels/ops.py): device launches counted by
`ops.dispatch_count()` over the window, per query completed in it."""


def read(r):
    return r.window["dispatches"] / r.queries if r.queries else None
