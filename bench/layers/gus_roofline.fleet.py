"""Dense GUS inside the fleet's step program: real-row bytes over the
device time of the fleet runner (``_fleet_runner_impl``'s jitted
vmap-over-replications of the frame scan), in percent of the roofline."""
from bench.roofline import roofline_share


def read(ctx):
    return roofline_share(ctx, "gus_work", r"^jit_per_rep$")
