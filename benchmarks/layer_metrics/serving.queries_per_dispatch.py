"""Requests completed per unit of device work: the window's completed
requests over the delta of the program's ``exec.device.dispatch``
counter. Above 1 when fusion serves several requests per dispatch."""


def read(ctx):
    d = ctx.delta("dispatch")
    if d <= 0:
        return None
    return len(ctx.ok_records()) / d
