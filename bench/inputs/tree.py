"""The leaves of a checkpoint, named and shaped by the configuration's leaf
table.  A leaf is ``"normal"`` (standard normal times ``scale``) or
``"ones"``.  Under ``fsdp``-way sharding a leaf with a ``shard_axis`` is
split in ``fsdp`` along that axis; one without stays whole.  This process
builds the shares of the first ``chips`` of those shards.

The values come from the configuration's ``base_seed``; the run's seed
picks a sign for each ``"normal"`` leaf.  A leaf and its negative hold the
same magnitudes, with mirrored Lorenzo residuals, so every seed gives the
codec the same work and, but for the few residuals at the edge of the
code range, the same payload sizes.
"""

from functools import partial

import numpy as np

from bench.session import seed_key


def shapes(spec: dict, chips: int) -> dict:
    fsdp = int(spec.get("fsdp", 1))
    if chips > fsdp or fsdp % chips:
        raise ValueError(f"{chips} chips cannot hold shares of fsdp={fsdp}")
    out = {}
    for leaf in spec["leaves"]:
        shape = list(leaf["shape"])
        ax = leaf.get("shard_axis")
        if ax is not None and fsdp > 1:
            if shape[ax] % fsdp:
                raise ValueError(f"leaf {leaf['name']!r}: axis {ax} of "
                                 f"{shape} does not split in {fsdp}")
            shape[ax] = shape[ax] // fsdp * chips
        out[leaf["name"]] = tuple(shape)
    return out


def signs(spec: dict, seed: int) -> np.ndarray:
    """The sign of each leaf a seed picks (+1 for ``"ones"`` leaves)."""
    rng = np.random.default_rng(seed)
    flip = rng.integers(0, 2, len(spec["leaves"]))
    return np.array([-1.0 if f and leaf.get("init", "normal") == "normal"
                     else 1.0 for f, leaf in zip(flip, spec["leaves"])],
                    np.float32)


def _make(key, sign, spec_items):
    import jax
    import jax.numpy as jnp

    out = {}
    for i, (name, shape, init, scale) in enumerate(spec_items):
        if init == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif init == "normal":
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * (scale * sign[i]))
        else:
            raise ValueError(f"leaf {name!r}: unknown init {init!r}")
    return out


def make(spec: dict, seed: int, chips: int = 1) -> dict:
    """All leaves in one jitted call; the signs are an argument, so every
    seed runs the same program."""
    import jax

    held = shapes(spec, chips)
    items = tuple((leaf["name"], held[leaf["name"]],
                   leaf.get("init", "normal"), float(leaf.get("scale", 1.0)))
                  for leaf in spec["leaves"])
    return jax.jit(partial(_make, spec_items=items))(
        seed_key(int(spec["base_seed"])), signs(spec, seed))
