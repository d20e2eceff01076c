"""Host self time of the explorer loop per generation in the window (ms):
each ``explorer.generation`` span less the union of the ``evo.*`` and
``engine.*`` spans inside it on its thread."""


def read(ctx):
    spans = ctx["spans"]
    gens = [s for s in spans if s["name"] == "explorer.generation"
            and s["ts"] >= ctx["t_open"] and s["ts"] + s["dur"] <= ctx["t_close"]]
    if not gens:
        return None
    kids = [s for s in spans if s["name"].startswith(("evo.", "engine."))]
    total = 0
    for g in gens:
        lo, hi = g["ts"], g["ts"] + g["dur"]
        inside = sorted((max(k["ts"], lo), min(k["ts"] + k["dur"], hi)) for k in kids
                        if k["tid"] == g["tid"] and k["ts"] < hi and k["ts"] + k["dur"] > lo)
        covered, end = 0, lo
        for s, e in inside:
            s = max(s, end)
            if e > s:
                covered += e - s
                end = e
        total += g["dur"] - covered
    return total / len(gens) / 1e6
