"""Mean number of sequences in a decode step over the traced part of the
window: the engine's ``serve.decode_batch`` histogram (scheduler and
engine layer). Moves tokens_per_s: each step serves more sequences."""


def read(ctx):
    h = (ctx.window.registry_delta or {}).get("serve.decode_batch")
    if not h or h["count"] <= 0:
        return None
    return h["sum"] / h["count"]
