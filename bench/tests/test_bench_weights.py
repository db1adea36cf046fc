"""Seeded weights: the program's tree and the reference's layers agree."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import weights as W

DATA = Path(__file__).resolve().parent / "data"


def test_program_tree_holds_the_reference_values():
    from repro.configs import get_smoke
    from repro.models import init_params

    spec = json.loads((DATA / "tiny.json").read_text())
    cfg = get_smoke(spec["program_arch"])
    abstract = jax.eval_shape(lambda k: init_params(k, cfg),
                              jax.random.key(0))
    key = W.seed_key(5_000_000_021)
    params = W.program_params(key, abstract, spec)
    layer = params["segments"][0]["0_dense"]
    # the same draws; XLA may fuse the scaling differently in the stacked
    # and the single-layer program, which moves the last bit only
    same = dict(rtol=1e-6, atol=0)
    for i in range(spec["num_hidden_layers"]):
        ref = W.layer_weights(key, i, spec)
        np.testing.assert_allclose(layer["attn"]["q"]["w"][i], ref["wq"],
                                   **same)
        np.testing.assert_allclose(layer["attn"]["k"]["b"][i], ref["bk"],
                                   **same)
        np.testing.assert_allclose(layer["mlp"]["down"]["w"][i], ref["wd"],
                                   **same)
        np.testing.assert_allclose(layer["ln2"]["g"][i], ref["ln2"], **same)
    g = W.global_weights(key, spec)
    np.testing.assert_allclose(params["embed"]["table"], g["embed"], **same)
    assert jax.tree.structure(params) == jax.tree.structure(abstract)
    other = W.program_params(W.seed_key(5_000_000_022), abstract, spec)
    assert not np.array_equal(other["embed"]["table"], g["embed"])


def test_a_leaf_without_a_role_is_an_error():
    spec = json.loads((DATA / "tiny.json").read_text())
    tree = {"embed": {"table": jax.ShapeDtypeStruct((512, 64), "float32")},
            "mystery": jax.ShapeDtypeStruct((3,), "float32")}
    with pytest.raises(ValueError, match="no role"):
        W.program_roles(tree, spec)
