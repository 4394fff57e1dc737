"""The one traffic generator: a ``ProductionSim`` and a ``DatasetSpec`` built
from a traffic file (``bench/traffic/<mix>.json``) and a configuration file.

The sim is the data platform the feed reads (an immutable UIH store filled by
compaction, the day's requests logged as training examples); its event
stream is also the source of truth the materialization check reads. Every
size comes from the traffic file, every draw from ``--seed``.
"""
from __future__ import annotations

import numpy as np


def seed32(seed: int) -> int:
    """A non-negative 31-bit seed derived from any whole number."""
    ss = np.random.SeedSequence(abs(int(seed)))
    return int(ss.generate_state(1, np.uint32)[0]) & 0x7FFFFFFF


def build_sim(sim: dict, seed: int):
    """One request day of a ``ProductionSim``: compaction of every earlier
    day into the immutable tier, that day's events in the mutable tier, and
    ``n_users * requests_per_user_day`` logged examples."""
    from repro.core import events as ev
    from repro.core.simulation import ProductionSim, SimConfig

    seed = abs(int(seed))
    day = int(sim["request_day"])
    out = ProductionSim(SimConfig(
        stream=ev.StreamConfig(
            n_users=int(sim["n_users"]), n_items=int(sim["n_items"]),
            days=day + 1,
            events_per_user_day_mean=float(sim["events_per_user_day"]),
            seed=seed),
        requests_per_user_day=int(sim["requests_per_user_day"]),
        lookback_ms=int(sim["lookback_days"]) * ev.MS_PER_DAY,
        seed=seed))
    out.run_day(day, capture_reference=False)
    return out


def dataset_spec(feed: dict, groups: dict, seq_len: int, seed: int,
                 min_rows: int):
    """The feed of one cell: ``groups`` maps a feature group to the UIH
    traits the model reads from it."""
    from repro.core.projection import TenantProjection
    from repro.data import DatasetSpec, SimSource
    from repro.dpp.featurize import FeatureSpec

    groups = {g: tuple(ts) for g, ts in groups.items()}
    tenant = TenantProjection("bench", seq_len=seq_len,
                              feature_groups=tuple(groups),
                              traits_per_group=groups)
    traits = tuple(t for ts in groups.values() for t in ts)
    return DatasetSpec(
        tenant=tenant,
        source=SimSource(min_rows=min_rows),
        batch_size=int(feed["batch_size"]),
        base_batch_size=int(feed["base_batch_size"]),
        prefetch_depth=int(feed["prefetch_depth"]),
        n_workers=int(feed["n_workers"]),
        buffer_batches=int(feed["buffer_batches"]),
        window_cache_size=int(feed["window_cache_size"]),
        reshuffle_seed=seed32(seed),
        features=FeatureSpec(seq_len=seq_len, uih_traits=traits,
                             candidate_fields=("item_id",),
                             label_fields=("click",)))


def init_params(key, shapes: dict):
    """Parameters in float32 from ``key`` for a tree of ``(shape, kind)``
    leaves: ``table`` N(0, 0.01^2), ``w`` N(0, 1/fan_in), ``zero``, ``one``.
    Call under ``jax.jit`` so the whole tree is made on the device at once."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], str))
    out = []
    for i, (shape, kind) in enumerate(leaves):
        if kind in ("zero", "one"):
            out.append(jnp.full(shape, kind == "one", jnp.float32))
        else:
            scale = 0.01 if kind == "table" else 1.0 / np.sqrt(shape[-2])
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32) * scale)
    return jax.tree.unflatten(tree, out)
