"""The plain reference against the program, on the CPU at a small size:
the bench's weights fit the program's layout, and with the program
computing in f32 its teacher-forced logits match the reference's."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(__file__)
SMALL = {"name": "small", "family": "encdec", "enc_layers": 2,
         "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512, "mlp_act": "relu",
         "tie_embeddings": True, "norm_eps": 1e-5, "rope_theta": 10000.0,
         "enc_len": 32}
FORMATS = {
    "int4": {"weights": "int4", "embed": "int8", "kv": "int8", "group": 64},
    "bf16": {"weights": "bf16", "embed": "bf16", "kv": "bf16", "group": 64},
}


def _family():
    path = os.path.join(HERE, "..", "families", "encdec.py")
    spec = importlib.util.spec_from_file_location("bench_family_encdec_t",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model():
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    return build_model(ModelConfig(**SMALL))


def test_weights_fit_the_program_layout():
    fam = _family()
    want = jax.eval_shape(_model().init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: fam.init_params(SMALL, k),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_follow_the_seed():
    fam = _family()
    a = fam.init_params(SMALL, jax.random.PRNGKey(3))
    b = fam.init_params(SMALL, jax.random.PRNGKey(3))
    c = fam.init_params(SMALL, jax.random.PRNGKey(4))
    assert np.array_equal(a["embedding"], b["embedding"])
    assert not np.array_equal(a["embedding"], c["embedding"])


def _program_and_reference(spec, kv=None):
    from repro.models import Ctx
    from repro.serving import deploy

    fam = _family()
    raw = fam.init_params(SMALL, jax.random.PRNGKey(11))
    pipe = deploy(_model().cfg, spec, params=raw, kv_dtype=kv,
                  ctx=Ctx(compute_dtype=jnp.float32), slots=4, max_len=24,
                  max_src_len=32, paged=True, page_size=8, horizon=4)
    rng = np.random.default_rng(0)
    src = rng.integers(4, 400, 20, dtype=np.int32)
    req = {"src_tokens": src[None], "tgt_in": np.full((1, 1), 450, np.int32)}
    stream = rng.integers(4, 400, 12, dtype=np.int32)
    got = np.asarray(pipe.engine.teacher_forced_logits([req], stream[None]))
    formats = dict(FORMATS[spec], **({"kv": kv} if kv else {}))
    W = fam.prepare(raw, formats)
    want = np.asarray(fam.logits(W, SMALL, formats, src, 450, stream))
    low = np.asarray(fam.logits(W, SMALL, formats, src, 450, stream,
                                control="fp8"))
    return got[0], want, low


@pytest.mark.parametrize("spec", ["int4", "bf16"])
def test_reference_is_the_program_in_f32(spec):
    """Weights in the configuration's stored formats, KV kept in f32:
    the program at f32 compute and the reference agree to rounding at
    the prefill's step and every decode step through the pages."""
    got, want, low = _program_and_reference(spec, kv="f32")
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-5 * scale
    assert np.abs(low - want).max() > 1e-3 * scale


@pytest.mark.parametrize("spec", ["int4", "bf16"])
def test_reference_stores_kv_as_the_program(spec):
    """With the configuration's KV format (int8 or bf16) the program
    also rounds attention probabilities to the stored width, which the
    reference, computing in f32, does not: the decode steps agree to
    that rounding, the prefill's step exactly."""
    got, want, _ = _program_and_reference(spec)
    scale = np.abs(want).max()
    assert np.abs(got[0] - want[0]).max() <= 1e-5 * scale
    assert np.abs(got - want).max() <= 1e-2 * scale
