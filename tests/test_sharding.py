"""Distribution layer: sharding rules + a real 8-device lowering (subprocess).

The in-process tests validate rule resolution on a 1-device mesh (shape
logic only); the subprocess test forces 8 host devices and actually
lowers + compiles a reduced train step and a decode step on a (4, 2)
(data, model) mesh — a miniature of the production dry-run.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core import PRESETS, quantize_tree
from repro.parallel.sharding import _leaf_spec


class _FakeMesh:
    shape = {"data": 4, "model": 2}
    axis_names = ("data", "model")


class _Leaf:
    def __init__(self, shape):
        self.shape = shape


@pytest.mark.parametrize("path,shape,expect", [
    ("['layers']['attn']['wq']", (48, 6144, 6144), (None, "data", "model")),
    ("['layers']['attn']['wo']", (48, 6144, 6144), (None, "model", "data")),
    ("['embedding']", (256000, 1024), ("model", "data")),
    ("['layers']['norm1_scale']", (48, 64), ()),   # no rule -> replicated
    ("['layers']['moe']['router']", (16, 64, 8), (None, None, None)),
])
def test_param_rules(path, shape, expect):
    spec = _leaf_spec(_FakeMesh(), path, _Leaf(shape), expert_axis=None)
    assert tuple(spec) == tuple(expect), (path, spec)


def test_expert_axis_no_reuse():
    spec = _leaf_spec(_FakeMesh(), "['moe']['experts']['w_gate']",
                      _Leaf((16, 64, 2048, 1408)), expert_axis="model")
    # expert dim takes "model"; the trailing ff dim must NOT reuse it
    assert tuple(spec) == (None, "model", "data", None)


def test_fsdp_scope_opt_only():
    p = "['params']['layers']['attn']['wq']"
    o = "['opt']['m']['layers']['attn']['wq']"
    sp = _leaf_spec(_FakeMesh(), p, _Leaf((48, 64, 64)), None, fsdp_scope="opt")
    so = _leaf_spec(_FakeMesh(), o, _Leaf((48, 64, 64)), None, fsdp_scope="opt")
    assert tuple(sp) == (None, None, "model")      # live params TP-only
    assert tuple(so) == (None, "data", "model")    # opt state FSDP-2D


def test_nondividing_dims_replicate():
    # vocab 51865 does not divide by 2 -> that dim replicates
    spec = _leaf_spec(_FakeMesh(), "['embedding']", _Leaf((51865, 512)), None)
    assert tuple(spec) == (None, "data")


def test_quantized_tree_shardable():
    """QTensor children resolve through the same rules (data vs scales)."""
    params = {"layers": {"attn": {"wq": jnp.ones((2, 64, 32))}}}
    qp = quantize_tree(params, PRESETS["int4"])
    from repro.parallel.sharding import param_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = param_shardings(mesh, qp)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    keys = {jax.tree_util.keystr(k): v for k, v in flat}
    assert any(".data" in k for k in keys)
    assert any(".scales" in k for k in keys)


@pytest.mark.slow
def test_eight_device_lowering_subprocess():
    """Miniature dry-run: 8 host devices, (4,2) mesh, train + decode."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import repro.configs.base as cb
        from repro.configs import get_config, reduce_config
        from repro.launch.dryrun import build_cell
        from repro.parallel import set_mesh

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for arch in ("internlm2-20b", "olmoe-1b-7b"):
            cfg = reduce_config(get_config(arch), d_model=64, num_layers=2,
                                num_heads=4, num_kv_heads=2, head_dim=16,
                                d_ff=96, vocab_size=256)
            cb.SHAPES["train_4k"] = cb.ShapeSpec("train_4k", 64, 8, "train")
            cb.SHAPES["decode_32k"] = cb.ShapeSpec("decode_32k", 64, 8,
                                                   "decode")
            for shp in ("train_4k", "decode_32k"):
                fn, shapes, in_sh, out_sh, donate = build_cell(
                    cfg, shp, mesh, "int4" if shp != "train_4k" else "bf16")
                with set_mesh(mesh):
                    c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                                donate_argnums=donate).lower(*shapes).compile()
                assert c.cost_analysis() is not None
                print("OK", arch, shp)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("OK") == 4
