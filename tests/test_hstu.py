"""HSTU on the CPU, in float32 at small sizes: the attention kernel
(interpret mode) against its dense oracle, forward and gradients, on
ragged, empty, one-event and full rows at several block sizes; the model's
loss and gradients against the benchmark's float32 reference
(``bench/configs/hstu_large.py``) on weights whose bias tables are drawn at
unit scale, so that the bias moves the result; and the same comparison
failing where the time term is dropped or one future position leaks."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.hstu_attention import hstu_attention as kern
from repro.kernels.hstu_attention import ops, ref
from repro.models.hstu import hstu_loss

ROOT = Path(__file__).resolve().parents[1]
DAY_S = 86_400


def _bench_config():
    path = ROOT / "bench" / "configs" / "hstu_large.py"
    spec = importlib.util.spec_from_file_location("bench_hstu_large", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rows(rng, lengths, length):
    """Right-aligned rows: timestamps in seconds, oldest first, over 20
    days, with ties; start = first valid position."""
    ts = np.zeros((len(lengths), length), np.int32)
    for r, n in enumerate(lengths):
        ts[r, length - n:] = np.sort(rng.integers(0, 20 * DAY_S, n))
        if n > 3:
            ts[r, -2] = ts[r, -3]                 # two events in one second
    start = np.array([length - n for n in lengths], np.int32)
    return jnp.asarray(ts), jnp.asarray(start)


def _attention_case(seed, lengths, length, heads=2, dqk=8, dv=16):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, length, heads, dqk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, length, heads, dqk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, length, heads, dv)), jnp.float32)
    ts, start = _rows(rng, lengths, length)
    rab_p = jnp.asarray(rng.standard_normal(length), jnp.float32)
    rab_t = jnp.asarray(rng.standard_normal(129), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((b, length, heads, dv)),
                      jnp.float32)
    return (q, k, v, ts, start, rab_p, rab_t), cot


CASES = {
    # lengths of the rows, positions, block
    "ragged_b8": ([29, 1, 0, 32, 9], 32, 8),
    "ragged_b16": ([29, 1, 0, 32, 9], 32, 16),
    "one_block": ([5, 16], 16, 16),
    "empty_and_one": ([0, 1], 32, 8),
    "full_b32": ([64, 64], 64, 32),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_dense_oracle(name):
    lengths, length, block = CASES[name]
    args, cot = _attention_case(len(name), lengths, length)
    q, k, v, ts, start, rab_p, rab_t = args

    def run(fn):
        def loss(q, k, v, rab_p, rab_t):
            return jnp.sum(fn(q, k, v, ts, start, rab_p, rab_t) * cot)
        out = jax.jit(fn)(q, k, v, ts, start, rab_p, rab_t)
        grads = jax.jit(jax.grad(loss, argnums=range(5)))(q, k, v, rab_p,
                                                          rab_t)
        return out, grads

    got, g_got = run(lambda *a: ops.hstu_attention(*a, block=block))
    want, g_want = run(ref.hstu_attention)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    pad = np.arange(length)[None, :] < np.asarray(start)[:, None]
    assert not np.any(np.asarray(got)[pad])               # padding is zero
    for g, w in zip(g_got, g_want):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(w))))


def test_toeplitz_blocks():
    rab = jnp.arange(1.0, 17.0)
    tiles = np.asarray(ops.toeplitz_blocks(rab, 4))
    for d in range(4):
        for a in range(4):
            for b in range(4):
                off = d * 4 + a - b
                assert tiles[d, a, b] == (rab[off] if off >= 0 else 0.0)


# ---------------------------------------------------------------------------
# the model against the benchmark's float32 reference
# ---------------------------------------------------------------------------

CONF = {"name": "hstu_tiny", "embed_dim": 16, "n_blocks": 2, "n_heads": 2,
        "dqk": 8, "dv": 8, "seq_len": 32, "item_vocab": 300,
        "num_negatives": 16, "temperature": 0.05, "time_buckets": 128,
        "compute_dtype": "float32"}
# float32 on the CPU: the program and the reference differ in the order of
# their sums only (blocked against dense attention), ~1e-6 of a leaf
TOL = 1e-4


def _model_case(seed=7):
    bench = _bench_config()
    from bench import gen

    rng = np.random.default_rng(seed)
    params = jax.jit(lambda k: gen.init_params(
        k, bench.param_shapes(CONF)))(jax.random.PRNGKey(seed))
    blocks = dict(params["blocks"])
    for name in ("rab_p", "rab_t"):                       # unit scale
        blocks[name] = jnp.asarray(rng.standard_normal(blocks[name].shape),
                                   jnp.float32)
    params = dict(params, blocks=blocks)
    lengths = [32, 20, 1, 0, 27, 2]
    length = CONF["seq_len"]
    ts, _ = _rows(rng, lengths, length)
    mask = np.arange(length)[None, :] >= (length - np.array(lengths))[:, None]
    ids = np.where(mask, rng.integers(1, CONF["item_vocab"], mask.shape), 0)
    neg = rng.integers(1, CONF["item_vocab"], CONF["num_negatives"])
    neg[0] = ids[0, 5]                                    # a gold collision
    batch = {"uih_item_id": jnp.asarray(ids, jnp.int32),
             "uih_timestamp": ts, "uih_mask": jnp.asarray(mask),
             "neg_ids": jnp.asarray(neg, jnp.int32)}
    return bench, params, batch


def _gaps(bench, params, batch) -> dict:
    """Worst relative gap of the loss and of each gradient leaf (norm of
    the difference over max(the reference leaf's norm, the median
    leaf's)), program against reference."""
    mcfg = bench.model_config(CONF)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: hstu_loss(p, batch, mcfg)))(params)
    den = bench.reference_denominator(batch, len(batch["uih_mask"]))
    with jax.default_matmul_precision("highest"):
        rloss, rgrads = jax.jit(jax.value_and_grad(
            lambda p: bench.reference_terms(p, batch, CONF) / den))(params)
    g = [np.asarray(x) for x in jax.tree.leaves(grads)]
    r = [np.asarray(x) for x in jax.tree.leaves(rgrads)]
    norms = np.array([np.linalg.norm(x) for x in r])
    floor = np.median(norms)
    diff = [np.linalg.norm(a - b) / max(n, floor)
            for a, b, n in zip(g, r, norms)]
    return {"loss": abs(float(loss) - float(rloss)) / abs(float(rloss)),
            "grad": max(diff)}


def test_model_matches_reference():
    gaps = _gaps(*_model_case())
    assert gaps["loss"] < TOL and gaps["grad"] < TOL, gaps


def _drop_time(monkeypatch):
    monkeypatch.setattr(kern, "time_bias",
                        lambda idx, table: jnp.zeros(idx.shape, jnp.float32))


def _leak_future(monkeypatch):
    monkeypatch.setattr(kern, "pair_mask",
                        lambda rows, cols, start: (cols <= rows + 1)
                        & (cols >= start))


@pytest.mark.parametrize("fault", [_drop_time, _leak_future],
                         ids=["time_term_dropped", "future_position_leaks"])
def test_reference_sees_the_fault(monkeypatch, fault):
    fault(monkeypatch)
    jax.clear_caches()          # the kernels are traced again, with it
    try:
        gaps = _gaps(*_model_case())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert max(gaps.values()) > 100 * TOL, gaps
