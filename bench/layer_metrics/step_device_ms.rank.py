"""Device time of the step's ``rank`` part per generation (ms): the union
of the intervals of the ops in scope ``rank``, summed over the generations
whose whole ``explorer.generation`` annotation the profile holds, over
their number.  Ops and annotations both come from the profile."""
import xtrace


def read(ctx):
    return xtrace.part_ms(ctx, "rank")
