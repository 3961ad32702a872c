"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program under a whole run at test widths on
the CPU (the harness's look for a chip stepped round), and the run's own
check has to catch it with the cell's limits: a token altered where it is
produced; a decode step that returns its state unchanged; half of the
batch left out of a decode step. (A one-chip cell has no exchange between
chips to leave out.)
"""
import pytest

import repro.serve.engine as engine_mod
from bench import manifest

CELLS = [w for w in manifest.json.loads(manifest.MANIFEST.read_text())
         ["workloads"]]
SERVING = [w["name"] for w in CELLS]


def token_altered(monkeypatch):
    emit = engine_mod.Engine._emit

    def wrong(self, seq, tok):
        emit(self, seq, (tok + 1) % self.model.cfg.vocab_size)

    monkeypatch.setattr(engine_mod.Engine, "_emit", wrong)


def _wrap_decode(monkeypatch, change):
    make = engine_mod.make_paged_decode

    def broken(*a, **kw):
        step = make(*a, **kw)

        def decode(params, tokens, cache, *rest):
            nxt, key, new_cache = step(params, tokens, cache, *rest)
            return change(nxt, tokens, cache, new_cache) + (key,)

        def ordered(*args):
            nxt, cache, key = decode(*args)
            return nxt, key, cache
        return ordered

    monkeypatch.setattr(engine_mod, "make_paged_decode", broken)


def state_unchanged(monkeypatch):
    _wrap_decode(monkeypatch, lambda nxt, toks, old, new: (nxt, old))


def half_batch_left_out(monkeypatch):
    """Every other slot keeps the token it was fed."""
    def change(nxt, toks, old, new):
        return nxt.at[1::2].set(toks[1::2, 0]), new
    _wrap_decode(monkeypatch, change)


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch_left_out])
@pytest.mark.parametrize("workload", SERVING)
def test_fault_is_not_correct(cpu_run, monkeypatch, fault, workload):
    fault(monkeypatch)
    # a load that keeps every slot busy, so each fault reaches the sample
    res, err = cpu_run(workload, rate_per_s=60.0)
    assert res["correct"] is False
    worst = max(v["value"] for v in res["check"].values())
    assert worst > min(v["limit"] for v in res["check"].values())
