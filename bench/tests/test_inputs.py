"""The seeded input makers: the same seed gives the same bytes, and every
seed the same work."""

import numpy as np
import pytest

from bench import harness, session

FIELD = {"kind": "field", "shape": [8, 8, 8], "dtype": "float32",
         "generator": "integrated_noise", "base_seed": 5}
TREE = {"kind": "tree", "dtype": "float32", "base_seed": 9, "fsdp": 4,
        "leaves": [{"name": "embed", "shape": [64, 12], "shard_axis": 0,
                    "init": "normal", "scale": 0.02},
                   {"name": "norm", "shape": [12], "init": "ones"},
                   {"name": "layers.w", "shape": [4, 12, 3, 8],
                    "shard_axis": 1, "init": "normal", "scale": 0.3},
                   {"name": "layers.odd", "shape": [3, 5], "init": "normal"},
                   {"name": "layers.v", "shape": [4, 6], "init": "normal"}]}

LAYOUT = harness.Layout()
field = LAYOUT.plugin("inputs", "field")
tree = LAYOUT.plugin("inputs", "tree")


def _bytes(d):
    return {k: np.asarray(v).tobytes() for k, v in d.items()}


@pytest.mark.parametrize("maker,spec", [(field, FIELD), (tree, TREE)],
                         ids=["field", "tree"])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**62 + 5])
def test_same_seed_same_bytes(maker, spec, seed):
    a = maker.make(spec, seed)
    b = maker.make(spec, seed)
    assert _bytes(a) == _bytes(b)


@pytest.mark.parametrize("maker,spec", [(field, FIELD), (tree, TREE)],
                         ids=["field", "tree"])
def test_seeds_pick_variants_of_the_same_values(maker, spec):
    """Seeds, beyond 32 bits too, give several variants; each holds the
    same magnitudes."""
    base = {k: np.sort(np.abs(np.asarray(v)), axis=None)
            for k, v in maker.make(spec, 0).items()}
    seen = set()
    for seed in [1, 2, 3, 4, 5, 2**32 + 1, 2**40 + 3, 2**62 + 7]:
        x = maker.make(spec, seed)
        for k, v in x.items():
            np.testing.assert_array_equal(
                np.sort(np.abs(np.asarray(v)), axis=None), base[k])
        seen.add(tuple(sorted(_bytes(x).items())))
    assert len(seen) >= 4


def test_field_is_integrated_noise_scaled_to_one():
    x = np.asarray(field.make(FIELD, 0)["field"])
    assert x.shape == (8, 8, 8) and x.dtype == np.float32
    assert np.isclose(np.abs(x).max(), 1.0, rtol=1e-6)
    # Integrated along every axis: neighbours are close.
    assert np.abs(np.diff(x, axis=2)).mean() < np.abs(x).mean()


@pytest.mark.parametrize("shape,n", [([8, 8, 8], 12), ([4, 8, 8], 4),
                                     ([4, 6, 8], 2), ([16], 2)])
def test_field_variants_keep_the_shape(shape, n):
    """A cube has 12 variants (axis order x sign); other shapes only the
    orders that keep them."""
    spec = {**FIELD, "shape": shape}
    assert len(field.variants(shape)) == n
    base = np.asarray(field.make(spec, 0)["field"])
    seen = set()
    for seed in range(n):
        x = np.asarray(field.make(spec, seed + 2**40)["field"])
        order, sign = field.variant(seed + 2**40, shape)
        assert x.shape == tuple(shape)
        np.testing.assert_array_equal(x, sign * base.transpose(order))
        seen.add(x.tobytes())
    assert len(seen) == n


def test_tree_seeds_flip_whole_normal_leaves():
    base = {k: np.asarray(v) for k, v in tree.make(TREE, 0).items()}
    for seed in (3, 2**33 + 1):
        signs = tree.signs(TREE, seed)
        x = tree.make(TREE, seed)
        for i, leaf in enumerate(TREE["leaves"]):
            want = base[leaf["name"]] * tree.signs(TREE, 0)[i] * signs[i]
            np.testing.assert_array_equal(np.asarray(x[leaf["name"]]), want)
    assert np.all(np.asarray(tree.make(TREE, 5)["norm"]) == 1.0)


def test_fsdp_quarter_shapes():
    shapes = tree.shapes(TREE, chips=1)
    assert shapes == {"embed": (16, 12), "norm": (12,),
                      "layers.w": (4, 3, 3, 8), "layers.odd": (3, 5),
                      "layers.v": (4, 6)}
    assert tree.shapes(TREE, chips=4)["embed"] == (64, 12)
    x = tree.make(TREE, 1)
    assert {k: v.shape for k, v in x.items()} == shapes
    std = float(np.asarray(x["embed"]).std())
    assert 0.01 < std < 0.03


def test_nest_flatten_round_trip():
    flat = {"a": 1, "b.c": 2, "b.d.e": 3}
    assert session.nest(flat) == {"a": 1, "b": {"c": 2, "d": {"e": 3}}}
    assert session.flatten(session.nest(flat)) == flat


def test_bad_seed_chips_and_axis_raise():
    with pytest.raises(ValueError):
        session.seed_key(-1)
    with pytest.raises(ValueError):
        tree.shapes(TREE, chips=3)
    bad = {**TREE, "leaves": [{"name": "w", "shape": [6, 5],
                               "shard_axis": 1}]}
    with pytest.raises(ValueError):
        tree.shapes(bad, chips=1)
