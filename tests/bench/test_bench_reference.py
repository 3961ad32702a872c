"""The plain reference against the program, at small sizes on the CPU: the
seeded payload has the layout ``quantize_model(pack=True)`` gives, decodes
to the weights the program serves, and the reference's logits match the
program's own float32 forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.arch import decoder


def test_payload_layout_matches_quantize_model(small_spec):
    """Same tree, shapes, dtypes and static layout as the quantizer's
    packed output for the recipe, at SMOKE-like widths."""
    from repro.core import vq_linear as vql
    from repro.core.pipeline import quantize_model
    from repro.core.recipe import get_recipe
    from repro.models import model_zoo

    spec = small_spec(n_layers=1)
    cfg = decoder.program_config(spec)
    model = model_zoo.build(cfg)
    dense = model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                              cfg.vocab_size)
    recipe = get_recipe("2.25bpv_2d").with_quantize_overrides(
        em_iters=1, codebook_update_iters=0)
    packed, _ = quantize_model(model, dense, toks, recipe=recipe, pack=True)
    ours = decoder.program_params(spec, decoder.raw_payload(spec, 3))

    def layout(tree):
        def one(x):
            if isinstance(x, vql.VQLinear):
                return ("vq", x.r, x.c, x.d, x.k, x.group_cols,
                        x.rows_per_band, x.scale_block, x.rule,
                        tuple((a.shape, str(a.dtype)) for a in (
                            x.words, x.codebooks, x.cb_scale, x.scale_sint,
                            x.scale_a, x.scale_z)))
            return (x.shape, str(x.dtype))
        return jax.tree.map(one, tree,
                            is_leaf=lambda x: isinstance(x, vql.VQLinear))

    assert layout(ours) == layout(packed)


def test_decode_matches_program_dequant(small_spec):
    from repro.core import vq_linear as vql

    spec = small_spec()
    raw = decoder.raw_payload(spec, 11)
    params = decoder.program_params(spec, raw)
    for name, (group, r, c) in spec.targets().items():
        leaf = params["layers"][group][name]
        one = jax.tree.map(lambda a: a[1], leaf)
        want = vql.dequantize(one, jnp.float32)
        got = reference.decode_matrix(
            {k: a[1] for k, a in raw["layers"][name].items()}, spec, r, c)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # decoded weights have the std of a 1/sqrt(in) init
        assert 0.5 < float(jnp.std(got)) * np.sqrt(c) < 2.0


def test_padded_vocab_rows_are_zero(small_spec):
    spec = small_spec(vocab=300)
    raw = decoder.raw_payload(spec, 5)
    assert raw["embed"].shape[0] == spec.padded_vocab > spec.vocab
    assert not np.asarray(raw["embed"][spec.vocab:]).any()


def test_seed_key_takes_large_seeds():
    a = weights.seed_key(2**33 + 5)
    b = weights.seed_key(5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(weights.seed_key(2**33 + 5)),
                                  np.asarray(a))


@pytest.mark.parametrize("tied", [True, False])
def test_reference_logits_match_program_forward(small_spec, tied):
    """The program's float32 forward (gather path, dense cache-free) on the
    seeded weights against the reference, at every position."""
    import dataclasses

    from repro.models import model_zoo

    spec = small_spec(tied=tied, qk_norm=tied)
    raw = decoder.raw_payload(spec, 7)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, raw)
    params = decoder.program_params(spec, f32)
    cfg = dataclasses.replace(decoder.program_config(spec),
                              dtype="float32")
    model = model_zoo.build(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, spec.vocab, n).astype(np.int32)
            for n in (37, 20)]
    positions = [np.arange(len(s)) for s in seqs]
    got = decoder.logits_at(raw, spec, seqs, positions, pad_to=16)
    with jax.default_matmul_precision("highest"):
        for s, g in zip(seqs, got):
            want, _, _ = model.forward(params, {"tokens": jnp.asarray(s[None])})
            want = np.asarray(want[0], np.float64)
            scale = np.abs(want).max()
            assert np.abs(g - want).max() <= 1e-4 * scale


def test_control_is_lower_precision(small_spec):
    """The fp8 control differs from the reference by far more than float32
    rounding, and by little enough to stay a forward of the same model."""
    spec = small_spec()
    raw = decoder.raw_payload(spec, 9)
    seq = np.arange(40, dtype=np.int32) % spec.vocab
    pos = [np.arange(40)]
    ref = decoder.logits_at(raw, spec, [seq], pos)[0]
    ctl = decoder.logits_at(raw, spec, [seq], pos, precision="fp8")[0]
    rel = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < rel < 0.5
