"""Share of the ``evo.execute`` spans the program recorded in the window
that the profile holds as annotations with at least one device op inside
(1 when the profile covers the whole window)."""
import xtrace


def read(ctx):
    prof = xtrace.profile(ctx)
    if prof is None:
        return None
    return xtrace.execute_coverage(prof, ctx["spans"], ctx["t_open"], ctx["t_close"])
