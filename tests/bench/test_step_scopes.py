"""The engine's ``decode`` and ``chunk`` programs, compiled at test widths
on the CPU, carry the named scope of each layer in the ``op_name`` of
their optimized HLO (what ``bench/labels.py`` maps device operations
through): each layer's slice and write-back of the stacked K/V pool, the
page scatter, the attention call and every matmul."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import labels, serve as bserve, spec as bspec
from bench.arch import decoder

ROOT = Path(__file__).resolve().parents[2]

INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
MATMUL_SCOPES = {"attn_qkv", "attention", "attn_out", "mlp", "head"}
# attention's batched dots of the chunk program (a gather over the page
# table) come out of XLA's CPU passes without metadata
DOT_SCOPES = {"decode": MATMUL_SCOPES,
              "chunk": MATMUL_SCOPES - {"attention"}}


def _instructions(text):
    """(type, opcode, op_name path) of every instruction, fused
    computations included."""
    out = []
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        rhs = m.group(2)
        op = re.search(r" ([a-z][a-z0-9\-]*)\(", rhs)
        path = re.search(r'op_name="([^"]*)"', rhs)
        out.append((rhs.split(" ")[0], op.group(1) if op else "",
                    path.group(1) if path else ""))
    return out


def _shape(t):
    m = re.match(r"\w+\[([\d,]*)\]", t)
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else None


@pytest.fixture(scope="module")
def programs():
    import dataclasses

    cfg = bspec.load(ROOT / "bench" / "configs" / "qwen3-1.7b-vq.json")
    spec = dataclasses.replace(
        decoder.spec(cfg), d=256, n_layers=2, n_heads=4,
        n_kv=2, hd=64, d_ff=512, vocab=300, max_positions=512)
    cfg["engine"] = dict(cfg["engine"], vq_matmul_impl="xla",
                         paged_attn_impl="xla", prefill_chunk=16)
    eng = bserve.build_engine(
        decoder, spec, cfg, {"engine": {"max_batch": 4, "max_len": 64,
                               "num_blocks": 17}}, 7)
    B, P = eng.max_batch, eng.n_pages
    dec = eng._decode_fn.lower(
        eng.params, jnp.zeros((B, 1), jnp.int32), eng.cache,
        jnp.zeros((B,), jnp.int32), jnp.zeros((B, P), jnp.int32),
        jnp.zeros((B,), bool), eng.key, jnp.zeros((B,), jnp.float32))
    chunk = eng._prefill_fn.lower(
        eng.params, jnp.zeros((1, 16), jnp.int32), eng.cache, 0, 0, 15,
        jnp.zeros((B, P), jnp.int32))
    pool = eng.cache.k.shape              # (layers, blocks, page, KV, hd)
    return ({"decode": dec.compile().as_text(),
             "chunk": chunk.compile().as_text()}, pool, eng)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_pool_slice_and_write_back_are_scoped(programs, program):
    texts, pool, _ = programs
    ins = _instructions(texts[program])
    reads = [p for t, op, p in ins if op == "dynamic-slice"
             and _shape(t) in (pool[1:], (1,) + pool[1:])]
    writes = [p for t, op, p in ins if op == "dynamic-update-slice"
              and _shape(t) == pool]
    # the K and V pools of the layer scan, at least
    assert len(reads) >= 2 and len(writes) >= 2
    assert all("/layer_cache_read/" in p for p in reads)
    assert all("/layer_cache_write/" in p for p in writes)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_matmuls_and_attention_are_scoped(programs, program):
    texts = programs[0]
    ins = _instructions(texts[program])
    # every dot that keeps an op_name is under a layer's scope
    dots = [p for _, op, p in ins if op == "dot" and p]
    for p in dots:
        assert MATMUL_SCOPES & set(p.split("/")), p
    scoped = {s for p in dots for s in p.split("/")} & MATMUL_SCOPES
    assert scoped == DOT_SCOPES[program]
    scatters = [p for _, op, p in ins if op == "scatter"
                and "/attention/" not in p]
    assert scatters and all("/kv_write/" in p or "/restore_masked/" in p
                            or "/slot_merge/" in p for p in scatters)


def test_step_program_boundaries_are_scoped(programs):
    texts = programs[0]
    for program, names in [
            ("decode", {"push_page_table", "embed", "kv_write", "attention",
                        "head", "sample", "restore_masked"}),
            ("chunk", {"push_page_table", "slot_view", "embed", "kv_write",
                       "attention", "head", "slot_merge"})]:
        paths = set(labels.op_scopes(texts[program]).values())
        segments = {s for p in paths for s in p.split("/")}
        assert names <= segments, names - segments


def test_per_layer_list_path_is_scoped(programs):
    """Layer trees kept as a list (a mixed recipe's heterogeneous packed
    metadata) over the stacked pool: the per-layer slice and write-back
    carry the same scopes as in the layer scan."""
    eng = programs[2]
    layers = eng.params["layers"]
    params = dict(eng.params, layers=[
        jax.tree.map(lambda a, i=i: a[i], layers)
        for i in range(eng.cache.k.shape[0])])
    B = eng.max_batch

    def fwd(params, cache):
        return eng.model.forward(params, {"tokens": jnp.zeros((B, 1),
                                                               jnp.int32)},
                                 cache=cache, pos=jnp.zeros((B,), jnp.int32),
                                 paged_impl="xla", vq_matmul_impl="xla")[:2]

    text = jax.jit(fwd).lower(params, eng.cache).compile().as_text()
    segments = {s for p in labels.op_scopes(text).values()
                for s in p.split("/")}
    assert {"layer_cache_read", "layer_cache_write", "attention",
            "mlp"} <= segments
