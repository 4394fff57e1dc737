"""Distributed-correctness tests on an 8-device (2 data x 4 model) host mesh.

Run in a subprocess so XLA_FLAGS can force multiple host devices without
affecting the rest of the suite (which must see 1 device).

Verified invariants:
  * row-sharded shard_map embedding paths == plain gather paths (bitwise-ish)
  * 2D expert-sharded MoE == FSDP shard_map MoE == dense oracle
  * transformer loss under a 2x4 mesh == single-device loss
  * recsys forward with mesh-enabled config == mesh-free config
"""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import dataclasses
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)

    # ---- 1) row-sharded embedding vs plain ----
    from repro.models.embedding import (bag_rowsharded, embedding_bag,
                                        lookup_rowsharded, seq_rowsharded)
    table = jax.random.normal(key, (64, 16), jnp.float32)
    ids = jax.random.randint(key, (8, 5), 0, 64)
    mask = jax.random.bernoulli(key, 0.8, (8, 5))
    with jax.set_mesh(mesh):
        got = jax.jit(lambda t, i, m: bag_rowsharded(
            t, i, m, "mean", mesh, ("data",)))(table, ids, mask)
    want = embedding_bag(table, ids, mask, "mean")
    # atol floor: the psum reduction order differs from the plain gather's
    # sum on some backends, leaving float32-epsilon noise near zero
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    with jax.set_mesh(mesh):
        got2 = jax.jit(lambda t, i: seq_rowsharded(t, i, mesh, ("data",)))(
            table, ids)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(table[ids]),
                               rtol=1e-6, atol=1e-6)
    print("embedding OK")

    # ---- 2) MoE: 2d == fsdp == oracle ----
    from repro.models.moe import MoEConfig, init_moe, moe_ffn, moe_ref
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=8, capacity_factor=8.0)
    p = init_moe(key, 16, cfg)
    x = jax.random.normal(key, (16, 16), jnp.float32)
    want = moe_ref(p, x, cfg)
    with jax.set_mesh(mesh):
        got_fsdp = jax.jit(lambda p, x: moe_ffn(p, x, cfg, mesh=mesh))(p, x)
        cfg2d = dataclasses.replace(cfg, ep_mode="2d")
        got_2d = jax.jit(lambda p, x: moe_ffn(p, x, cfg2d, mesh=mesh))(p, x)
    np.testing.assert_allclose(np.asarray(got_fsdp), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_2d), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("moe OK")

    # ---- 3) transformer loss: mesh == single device ----
    from repro.models.transformer import TransformerConfig, init, loss_fn
    from repro.launch.shardings import lm_param_specs
    tc = TransformerConfig("t", n_layers=2, d_model=32, n_heads=4,
                           n_kv_heads=2, d_ff=64, vocab=96, head_dim=8,
                           qk_norm=True, compute_dtype=jnp.float32,
                           q_chunk=8, loss_chunk=8)
    params = init(key, tc)
    toks = jax.random.randint(key, (4, 16), 0, 96)
    tgt = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 96)
    base = float(loss_fn(params, toks, tgt, tc))
    pspec = lm_param_specs(params, mesh)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda p, a, b: loss_fn(p, a, b, tc, mesh=mesh),
                    in_shardings=(pspec, P("data", None), P("data", None)))
        dist = float(f(params, toks, tgt))
    assert abs(base - dist) < 1e-4, (base, dist)
    print("transformer OK")

    # ---- 4) recsys forward: mesh cfg == plain cfg ----
    from repro.models import recsys as R
    rc = R.DLRMUIHConfig(name="t", seq_len=16, d_seq=16, n_seq_layers=1,
                         n_heads=2, n_dense=4, n_sparse=2, embed_dim=8,
                         item_vocab=256, field_vocab=64,
                         compute_dtype=jnp.float32, remat=False)
    rp = R.init_dlrm_uih(key, rc)
    batch = {
        "uih_item_id": jax.random.randint(key, (8, 16), 0, 256),
        "uih_action_type": jax.random.randint(key, (8, 16), 0, 16),
        "uih_mask": jnp.ones((8, 16), bool),
        "cand_item_id": jax.random.randint(key, (8,), 0, 256),
        "sparse_ids": jax.random.randint(key, (8, 2), 0, 64),
        "dense": jax.random.normal(key, (8, 4), jnp.float32),
    }
    want = R.dlrm_uih_forward(rp, batch, rc)
    rc_mesh = dataclasses.replace(rc, mesh=mesh, data_axes=("data",))
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, b: R.dlrm_uih_forward(p, b, rc_mesh))(rp, batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("recsys OK")
    print("ALL DISTRIBUTED CHECKS PASSED")
""")


@pytest.mark.slow
def test_distributed_correctness_8dev():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # host-device test: never let jax probe for real accelerators
             "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    assert "ALL DISTRIBUTED CHECKS PASSED" in proc.stdout
