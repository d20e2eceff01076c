"""Device time per generation (ms): the summed durations of every device
operation in the profiled window over the generations in it."""


def read(ctx):
    gens = ctx["window"].gens
    ops = ctx["device_trace"]["op_ns"]
    if not gens or not ops:
        return None
    return sum(ops.values()) / gens / 1e6
