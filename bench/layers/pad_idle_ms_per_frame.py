"""Device idle under the online decision's input padding
(``gus/pad``: ``pad_instance``'s eager per-leaf copies and concatenates),
per frame, in ms."""
from bench.spans import per_frame_ms


def read(ctx):
    return per_frame_ms(ctx, ("gus/pad",))
