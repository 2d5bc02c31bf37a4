"""Dense GUS in the online controller's per-frame program
(``_gus_schedule_xla`` or ``_gus_schedule_pallas``): real-row bytes over its
device time, in percent of the roofline."""
from bench.roofline import roofline_share


def read(ctx):
    return roofline_share(ctx, "gus_work", r"_gus_schedule_(xla|pallas)$")
