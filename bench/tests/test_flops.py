"""Each configuration's useful-FLOP count against a count made by hand at
a tiny shape: every matmul written out, valid positions only."""
import numpy as np

from bench import gen
from bench.tests.tiny import cell as tiny_cell


def _cell(name):
    return tiny_cell(name)


def test_bert4rec_hand_count():
    cell = _cell("bert4rec.short_seq")
    c = dict(cell.config, embed_dim=4, n_blocks=1, item_vocab=7)
    mask = np.zeros((2, 5), bool)
    mask[0, -4:] = True                   # 4 valid, bidirectional: 16 pairs
    mask[1, -2:] = True                   # 2 valid: 4 pairs
    mpos = np.zeros((2, 5), bool)
    mpos[0, -1] = mpos[0, -3] = True      # 2 masked positions
    mpos[1, -1] = True
    d = 4
    tok = 4 * 2 * d * d + 3 * 2 * d * 4 * d
    # logits over the whole table (7 rows) at masked positions only
    want = [3 * (4 * tok + 16 * 4 * d + 2 * 7 * 2 * d),
            3 * (2 * tok + 4 * 4 * d + 1 * 7 * 2 * d)]
    got = cell.model.flops_per_row({"uih_mask": mask, "mask_pos": mpos}, c)
    assert list(got) == want


def test_params_match_the_program_layout():
    """The benchmark makes the parameters; they must be the tree, shapes
    and dtypes that the program's own init builds."""
    import jax

    from repro.models import recsys as R

    cell = _cell("bert4rec.short_seq")
    mcfg = cell.model.model_config(cell.config)
    want = jax.eval_shape(lambda: R.init_bert4rec(jax.random.PRNGKey(0),
                                                  mcfg))
    got = jax.eval_shape(lambda: gen.init_params(
        jax.random.PRNGKey(0), cell.model.param_shapes(cell.config)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, got, want)))
