"""Per-architecture smoke tests: instantiate the REDUCED config of every
assigned arch (+ the paper's own), run one step per shape kind on CPU via the
same cell builders the dry-run uses, assert output shapes + finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.launch.mesh import make_test_mesh
from repro.launch.sampling import sample_args
from repro.launch.steps import build_cell

ARCHS = list_archs()


def _finite(tree) -> bool:
    return all(
        bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
    )


def _run(arch_id: str, shape_name: str):
    spec = get_arch(arch_id)
    mesh = make_test_mesh(1)
    cell = build_cell(spec, shape_name, mesh, use_full=False)
    args = sample_args(cell, spec.family, seed=0)
    with jax.set_mesh(mesh):
        out = jax.jit(cell.step_fn)(*args)
    return cell, out


# -- one train-shape test per arch (all 11) -----------------------------------

TRAIN_SHAPE = {
    "lm": "train_4k",
    "gnn": "full_graph_sm",
    "recsys": "train_batch",
}


@pytest.mark.parametrize("arch_id", ARCHS)
def test_train_step_smoke(arch_id):
    spec = get_arch(arch_id)
    cell, out = _run(arch_id, TRAIN_SHAPE[spec.family])
    params, opt_state, metrics = out
    assert float(metrics["loss"]) > 0
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert _finite(params), "non-finite params after update"
    assert int(opt_state.step) == 1


# -- serving kinds -------------------------------------------------------------

LM_ARCHS = [a for a in ARCHS if get_arch(a).family == "lm"]
RECSYS_ARCHS = [a for a in ARCHS if get_arch(a).family == "recsys"]


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_prefill_smoke(arch_id):
    cell, (logits, cache) = _run(arch_id, "prefill_32k")
    cfg = cell.meta["cfg"]
    assert logits.shape[-1] == cfg.vocab
    assert _finite(logits)
    assert _finite(cache)


@pytest.mark.parametrize("arch_id", LM_ARCHS)
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_lm_decode_smoke(arch_id, shape):
    cell, (logits, cache) = _run(arch_id, shape)
    cfg = cell.meta["cfg"]
    assert logits.shape[-1] == cfg.vocab
    assert _finite(logits)


@pytest.mark.parametrize("arch_id", RECSYS_ARCHS)
def test_recsys_serve_smoke(arch_id):
    cell, out = _run(arch_id, "serve_p99")
    assert _finite(out)
    b = cell.meta["batch"]
    assert out.shape[0] == b


@pytest.mark.parametrize("arch_id", RECSYS_ARCHS)
def test_recsys_retrieval_smoke(arch_id):
    cell, out = _run(arch_id, "retrieval_cand")
    assert _finite(out)
    n = cell.meta["n_candidates"]
    assert out.shape[-1] == n or out.shape[0] == n


def test_gnn_all_shapes_smoke():
    for shape in ["full_graph_sm", "minibatch_lg", "ogb_products", "molecule"]:
        cell, (params, opt, metrics) = _run("meshgraphnet", shape)
        assert np.isfinite(float(metrics["loss"])), shape


def test_gnn_neighbor_sampler_real():
    """minibatch_lg path: sample a real subgraph from a random parent graph and
    run a train step on it."""
    from repro.models.gnn import CSRGraph, sample_subgraph

    rng = np.random.default_rng(0)
    n_parent, e_parent = 500, 4000
    senders = rng.integers(0, n_parent, e_parent)
    receivers = rng.integers(0, n_parent, e_parent)
    g = CSRGraph(n_parent, senders, receivers)
    seeds = rng.choice(n_parent, size=16, replace=False)
    sub = sample_subgraph(g, seeds, fanouts=(3, 2), rng=rng)
    assert len(sub["senders"]) == len(sub["receivers"]) == 16 * 3 + 16 * 3 * 2
    assert sub["senders"].max() < len(sub["nodes"])
    # all sampled edges exist in the parent graph
    parent_edges = set(zip(senders.tolist(), receivers.tolist()))
    ns = sub["nodes"]
    for s, r, ok in zip(sub["senders"], sub["receivers"], sub["edge_mask"]):
        if ok:
            assert (int(ns[s]), int(ns[r])) in parent_edges
