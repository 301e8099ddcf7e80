"""Device time of the jitted batched decode-step program
(``Engine._decode_active``) per execution, in ms."""

PROGRAM = "decode_active_impl"


def read(run):
    if run.trace is None:
        return None
    times = run.trace.program_s(PROGRAM)
    if not times:
        return None
    return sum(times) / len(times) * 1e3
