"""Total ``engine.sim_patch`` time after the window: the batched
simulation of the final candidates' ``sim_period`` (s)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "engine.sim_patch"
         and s["ts"] >= ctx["t_close"]]
    return sum(d) / 1e9 if d else None
