"""Total ``evo.hypervolume`` time after the window: the relative
hypervolume of every generation's archive against the final front (s)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "evo.hypervolume"
         and s["ts"] >= ctx["t_close"]]
    return sum(d) / 1e9 if d else None
