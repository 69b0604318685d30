"""Serving params built one period at a time (``serve/engine.init_serving``)
are the codes and scales of converting the whole master tree at once."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve.engine import init_serving, ternarize_model


@pytest.mark.parametrize("pack", [False, True], ids=["int8", "packed"])
@pytest.mark.parametrize("arch", ["chatglm3-6b", "granite-moe-3b-a800m"])
def test_init_serving_matches_whole_tree_conversion(arch, pack):
    cfg = get_config(arch, smoke=True)
    cfg = cfg.replace(ternary=cfg.ternary.replace(
        enabled=True, encoding="asymmetric", act_mode="ternary", pack=pack))
    key = jax.random.PRNGKey(3)
    want = ternarize_model(tfm.init(cfg, key), cfg)
    got = init_serving(cfg, key)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
