"""FLOP and byte counts against hand arithmetic, for both
configurations, and the configuration files against the program."""

import json
import os

import pytest

from bench import counts

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def _conf(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


INT4 = _conf("nllb200-600m-int4")
BF16 = _conf("nllb200-600m-bf16")

# NLLB-200-distilled-600M: 12 decoder layers, d 1024, 16 heads of 64,
# FFN 4096, vocab 256206. A decode step reads per layer self q/k/v/o
# (4 d^2), cross q/o (2 d^2) and the FFN (2 d ff).
DEC_LINEAR = 12 * (6 * 1024 * 1024 + 2 * 1024 * 4096)      # 176160768
HEAD = 256206 * 1024                                      # 262354944


def test_params():
    for c in (INT4, BF16):
        assert counts.decoder_linear_params(c["model"]) == DEC_LINEAR
        assert counts.head_params(c["model"]) == HEAD
    assert DEC_LINEAR == 176160768 and HEAD == 262354944


def test_token_flops():
    # 2 per multiply-add of the linears and the head, plus q.k and p.v
    # over 11 self positions (j = 10 and itself) and 32 source positions
    want = 2 * (DEC_LINEAR + HEAD) + 2 * 2 * 12 * 16 * 64 * (11 + 32)
    assert want == 879144960
    for c in (INT4, BF16):
        assert counts.token_flops(c["model"], 10, 32) == want


def test_step_weight_bytes():
    norms = (3 * 12 + 1) * 1024 * 2
    # int4 weights: half a byte plus a 4-byte scale per block of 64;
    # int8 head: one byte plus the same scale share
    int4 = DEC_LINEAR * (0.5 + 4 / 64) + HEAD * (1 + 4 / 64) + norms
    assert int4 == 377918336
    assert counts.step_weight_bytes(INT4["model"], INT4["formats"]) == int4
    bf16 = (DEC_LINEAR + HEAD) * 2 + norms
    assert bf16 == 877107200
    assert counts.step_weight_bytes(BF16["model"], BF16["formats"]) == bf16


def test_token_kv_bytes():
    # 10 cached self positions, 32 source positions, 1 new entry; K and
    # V for 16 heads of 64 in 12 layers; int8 adds a 4-byte scale per
    # token and head
    assert counts.token_kv_bytes(INT4["model"], INT4["formats"], 10, 32) \
        == 12 * 2 * 16 * (64 + 4) * 43 == 1122816
    assert counts.token_kv_bytes(BF16["model"], BF16["formats"], 10, 32) \
        == 12 * 2 * 16 * 128 * 43 == 2113536


def test_counts_take_arrays():
    f = counts.token_flops(INT4["model"], [1, 2], [16, 16])
    assert f[1] - f[0] == 2 * 2 * 12 * 16 * 64


@pytest.mark.parametrize("conf", [INT4, BF16], ids=["int4", "bf16"])
def test_formats_match_the_program_spec(conf):
    """The formats the yardstick counts with are the ones deploy() serves
    the configuration's spec with."""
    from repro.core.spec import resolve_spec

    spec = resolve_spec(conf["spec"])
    f = conf["formats"]
    assert (spec.weights, spec.embed, spec.kv) == \
        (f["weights"], f["embed"], f["kv"])
    if spec.weights != "bf16":
        assert spec.group == f["group"]


@pytest.mark.parametrize("conf", [INT4, BF16], ids=["int4", "bf16"])
def test_published_sizes(conf):
    m, p = conf["model"], conf["published"]
    assert conf["reduced"] == []
    assert m["enc_layers"] == p["encoder_layers"] == 12
    assert m["num_layers"] == p["decoder_layers"] == 12
    assert m["d_model"] == p["d_model"] == 1024
    assert m["num_heads"] == p["decoder_attention_heads"] == 16
    assert m["num_heads"] * m["head_dim"] == m["d_model"]
    assert m["d_ff"] == p["decoder_ffn_dim"] == p["encoder_ffn_dim"] == 4096
    assert m["vocab_size"] == p["vocab_size"] == 256206
    assert m["mlp_act"] == p["activation_function"] == "relu"
    assert m["tie_embeddings"] is True
