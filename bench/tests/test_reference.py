"""The benchmark's weights and float32 reference against the program's
own model at its reduced presets (the program's forward is the witness
here; the reference itself imports nothing of it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.archs import dense_gqa

SEED = 2**33 + 5


def _tiny(arch):
    from repro.configs import get_reduced
    cfg = get_reduced(arch)
    mc = {k: getattr(cfg, f) for k, (f, _) in dense_gqa.HF_KEYS.items()
          if k != "hidden_act"}
    mc["hidden_act"] = "silu"
    return cfg, mc


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "yi-34b"])
def test_weights_are_the_program_tree(arch):
    from repro.models import registry
    cfg, mc = _tiny(arch)
    p = weights.make_params(SEED, mc)
    want = jax.eval_shape(lambda k: registry.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(p) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    one = weights.layer_f32(SEED, mc, 1)
    np.testing.assert_array_equal(
        one["mlp/down/w"], np.asarray(p["layers"]["mlp"]["down"]["w"][1],
                                      np.float32))
    other = weights.make_params(SEED + 1, mc)
    assert not np.array_equal(np.asarray(other["embed"], np.float32),
                              np.asarray(p["embed"], np.float32))


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "yi-34b"])
def test_reference_matches_the_program_forward_in_float32(arch):
    from repro.models import transformer
    cfg, mc = _tiny(arch)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, mc["vocab_size"], n) for n in (300, 150, 40)]
    out = reference.logit_gaps(SEED, mc, seqs, 512)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                       weights.make_params(SEED, mc))
    with jax.default_matmul_precision("highest"):
        for s, o in zip(seqs, out["offsets"]):
            lg = np.asarray(transformer.forward(p32, cfg, jnp.asarray(s)[None])[0])
            np.testing.assert_allclose(out["best"][o:o + len(s)], lg.max(-1),
                                       atol=2e-5, rtol=2e-5)
            np.testing.assert_allclose(
                out["at_next"][o:o + len(s) - 1],
                lg[np.arange(len(s) - 1), s[1:]], atol=2e-5, rtol=2e-5)


def test_control_departs_from_the_reference():
    _, mc = _tiny("qwen2.5-32b")
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, mc["vocab_size"], 400)]
    out = reference.logit_gaps(SEED, mc, seqs, 512, control=True)
    gap = out["best"][:399] - out["at_control"][:399]
    assert gap.min() >= 0 and gap.max() > 0.1
