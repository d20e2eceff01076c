"""Mean host CAPS-HMS decode (``engine.decode`` span) after the window,
while ``explore()`` re-evaluates the final archive and survivors (ms)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "engine.decode"
         and s["ts"] >= ctx["t_close"]]
    return sum(d) / len(d) / 1e6 if d else None
