"""Device idle time per engine tick that the engine's own host phases
leave: the idle gaps of the traced window under a ``serve.*`` span (the
tick, admission, prefill dispatch, prompt sampling, decode input
preparation, the token download, token emission), over the ``serve.tick``
steps in the window (bench/labels.py). Idle time under the harness's
``bench.*`` spans or under no span is not the engine's and is left out.
Moves tokens_per_s: a device that waits on the host serves fewer ticks."""
from bench import labels


def read(ctx):
    idle, ticks = labels.idle_in_spans(ctx.trace, "serve.")
    if ticks == 0:
        return None
    return 1e3 * sum(idle.values()) / ticks
