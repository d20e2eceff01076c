"""Mean host CAPS-HMS decode (``engine.decode`` span) inside the window (ms)."""


def read(ctx):
    d = [s["dur"] for s in ctx["spans"] if s["name"] == "engine.decode"
         and ctx["t_open"] <= s["ts"] and s["ts"] + s["dur"] <= ctx["t_close"]]
    return sum(d) / len(d) / 1e6 if d else None
