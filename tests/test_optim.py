"""Optimizer substrate: AdamW, 8-bit states, schedules, grad compression."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import (adamw_init, adamw_update, compressed_psum,
                         warmup_cosine, warmup_linear)


def _run_adam(state_bits, steps=25):
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)}
    state = adamw_init(params, state_bits=state_bits)
    traj = []
    for i in range(steps):
        g = {"w": jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)}
        params, state, _ = adamw_update(g, state, params, lr=1e-2,
                                        state_bits=state_bits)
        traj.append(np.asarray(params["w"]))
    return traj


def test_8bit_states_track_fp32():
    """Blockwise-int8 moments stay close to the fp32 optimizer trajectory."""
    t32 = _run_adam(32)
    t8 = _run_adam(8)
    rel = np.linalg.norm(t8[-1] - t32[-1]) / np.linalg.norm(t32[-1])
    assert rel < 0.05, rel


def test_8bit_state_memory():
    params = {"w": jnp.zeros((1024, 256), jnp.float32)}
    s32 = adamw_init(params, state_bits=32)
    s8 = adamw_init(params, state_bits=8)
    b32 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(s32))
    b8 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(s8))
    assert b8 < 0.35 * b32   # ~2.06 vs 8 bytes/param


def test_master_weights_update_bf16_params():
    params = {"w": jnp.ones((32, 16), jnp.bfloat16)}
    state = adamw_init(params, master=True)
    g = {"w": jnp.full((32, 16), 0.5, jnp.float32)}
    new_p, new_s, _ = adamw_update(g, state, params, lr=1e-3)
    assert new_p["w"].dtype == jnp.bfloat16
    assert new_s["master"]["w"].dtype == jnp.float32
    assert float(new_s["master"]["w"][0, 0]) < 1.0   # actually stepped


def test_grad_clipping():
    params = {"w": jnp.zeros((8,), jnp.float32)}
    state = adamw_init(params)
    g = {"w": jnp.full((8,), 100.0)}
    _, _, m = adamw_update(g, state, params, lr=1e-3, clip_norm=1.0)
    assert float(m["grad_norm"]) > 100


def test_schedules_shape():
    lr = [float(warmup_linear(s, peak_lr=1.0, warmup=10, total=100))
          for s in range(100)]
    assert lr[0] == 0 and abs(lr[10] - 1.0) < 1e-6 and lr[-1] < 0.05
    lc = [float(warmup_cosine(s, peak_lr=1.0, warmup=10, total=100))
          for s in range(100)]
    assert max(lc) <= 1.0 + 1e-6 and lc[50] > lc[90]


def test_compressed_psum_single_device():
    """shard_map over a 1-device mesh: compression is near-lossless psum."""
    mesh = jax.make_mesh((1,), ("dp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((256, 8)),
                          jnp.float32)}

    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda t: compressed_psum(t, "dp"), mesh=mesh,
                  in_specs=(P(),), out_specs=P())
    out = f(g)
    rel = float(jnp.linalg.norm(out["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 2e-2   # int8 grid error only
