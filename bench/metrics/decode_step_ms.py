"""Device time of one decode step: the jitted decode program's time on
the device trace over the number of times it ran. Moves tokens_per_s."""

PROGRAM = "decode"


def read(ctx):
    n = ctx.trace.program_count(PROGRAM)
    if n == 0:
        return None
    return 1e3 * ctx.trace.program_seconds(PROGRAM) / n
