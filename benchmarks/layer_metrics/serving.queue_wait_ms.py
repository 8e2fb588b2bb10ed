"""Mean wait in the serving admission queue per request (ms): the delta of
the program's ``serving.queue.wait`` histogram over the window, divided
by its observations."""


def read(ctx):
    n = ctx.delta("queue_wait_n")
    if n <= 0:
        return None
    return ctx.delta("queue_wait_s") / n * 1e3
