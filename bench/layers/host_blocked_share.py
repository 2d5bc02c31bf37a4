"""Share of each study's wall time in which the fleet pipeline was blocked
on the host: the up-front trace generation plus the consumer's wait for
each window (``fleet/generate_traces`` + ``fleet/window_wait``) over
``total_s``, summed over the window's studies, in percent."""


def read(ctx):
    t = ctx.get("timings")
    total = sum(d.get("total_s", 0.0) for d in t or ())
    if not total:
        return None
    blocked = sum(d.get("fleet/generate_traces", 0.0) + d.get("fleet/window_wait", 0.0)
                  for d in t)
    return 100.0 * blocked / total
