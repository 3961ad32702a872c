"""The engine's phases on a profiler trace taken on the CPU: each tick is
one ``serve.tick`` step, its phases are ``serve.<path>`` annotations, and
the trace holds no Python-tracer events (their names start with ``$``),
so the innermost host event at a device gap is a phase of the engine or
the runtime's own."""
import glob

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.configs import SMOKE
from repro.models import model_zoo
from repro.serve.engine import Engine, Request


def test_two_ticks_on_the_trace(tmp_path):
    cfg = SMOKE["llama2-7b"].scaled(dtype="float32", n_layers=2, d_model=64,
                                    vocab_size=256, max_seq_len=64)
    model = model_zoo.build(cfg)
    eng = Engine(model, model.init_params(jax.random.PRNGKey(0)),
                 max_batch=2, max_len=64, page_size=8)
    warm = Request(rid="warm", prompt=np.arange(1, 6, dtype=np.int32),
                   max_new_tokens=3)
    eng.run([warm])                       # compile outside the trace
    eng.submit(Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=4))
    t0 = eng.ticks
    eng.telemetry.spans.start_trace(str(tmp_path))
    eng.step()
    eng.step()
    eng.telemetry.spans.stop_trace()

    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    events = [e for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = [e.name for e in events]
    assert not [n for n in names if n.startswith("$")]
    steps = sorted(dict(e.stats)["step_num"] for e in events
                   if e.name == "serve.tick")
    assert steps == [t0, t0 + 1]
    assert "serve.admit" in names
    assert "serve.prefill" in names
    assert names.count("serve.decode_tick/emit") == 2
    assert "serve.decode_tick/device" in names
