"""Shared kernel execution policy: where do the Pallas kernels run, and
in what HBM layout do their wrappers hand them data?

Every public wrapper in ``kernels/*/ops.py`` asks :func:`interpret_default`
whether to pass ``interpret=True`` to ``pl.pallas_call``. On a TPU backend
the kernels are always compiled by Mosaic. Off-TPU they run in the Pallas
**interpreter**: the *same* kernel body (DMA windows, masks,
sequential-grid carries) executed on CPU — NOT a numpy reference fallback.
``ref.py`` modules exist only as oracles for the test sweeps; no wrapper
ever routes through them, so tier-1 CI exercises the real kernel logic on
every run (tests/test_kernels.py monkeypatches the refs to raise and proves
it). A run that must be on the chip checks ``jax.devices()[0].platform``
itself (``chip_smoke.py`` does): this policy never decides that.

Layout: a kernel that DMAs a row window at an arbitrary row offset reads an
(N, 128) array, one 128-lane column tile. Mosaic refuses such a DMA from a
sliced column range of a wider array (it cannot prove the row offset is a
multiple of the 8-row tiling), and on a v5e the kernel tests with
200-lane arrays read as one full-width window came back wrong.
:func:`to_lane_tiles` therefore lays an (N, D) array out as
(ceil(D/128), N, 128); for D <= 128 the split into tiles is a reshape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def on_tpu() -> bool:
    """True iff the default jax backend is a real TPU."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Whether ``pl.pallas_call`` should run in interpret mode by default."""
    return not on_tpu()


def tile_rows(n: int) -> int:
    """``n`` rounded up to whole 8-row sublane tiles."""
    return -(-n // 8) * 8


def to_lane_tiles(values: jax.Array, front_rows: int = 0) -> jax.Array:
    """(N, D) -> (ceil(D/128), front_rows + N, 128): ``front_rows`` zero rows
    in front, lanes zero-padded, split into 128-lane column tiles."""
    n, d = values.shape
    c = -(-d // LANES)
    v = jnp.pad(values, ((front_rows, 0), (0, c * LANES - d)))
    return v.reshape(front_rows + n, c, LANES).transpose(1, 0, 2)
