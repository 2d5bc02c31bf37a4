"""Device idle under the online decision's scheduler call (``gus/call``:
argument handling and dispatch of the jitted GUS program), per frame, in
ms."""
from bench.spans import per_frame_ms


def read(ctx):
    return per_frame_ms(ctx, ("gus/call",))
