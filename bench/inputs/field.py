"""One float32 field of ``shape`` holding integrated noise: standard normal,
cumulative sums along every axis, scaled to max |x| 1, the
Lorenzo-predictable surrogate the codec is sized against.

The noise comes from the configuration's ``base_seed``; the run's seed
picks one of the field's symmetric variants, a sign and an order of its
axes that keeps the shape (any order of a cube's axes; for 100x500x500,
the last two swapped or not).  Every variant holds the same values in
another order, with the same multiset of Lorenzo residuals, so every seed
gives the codec the same work and the same payload sizes.
"""

import itertools

from bench.session import seed_key


def shapes(spec: dict, chips: int) -> dict:
    return {"field": tuple(spec["shape"])}


def variants(shape) -> list:
    """Every ``(axis order, sign)`` that keeps the field's shape."""
    orders = [p for p in itertools.permutations(range(len(shape)))
              if all(shape[a] == shape[i] for i, a in enumerate(p))]
    return [(o, s) for s in (1.0, -1.0) for o in orders]


def variant(seed: int, shape) -> "tuple[tuple, float]":
    """The ``(axis order, sign)`` a seed picks."""
    vs = variants(shape)
    return vs[seed % len(vs)]


def _integrated_noise(key, shape):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(key, shape, jnp.float32)
    for ax in range(len(shape)):
        x = jnp.cumsum(x, axis=ax)
    return x / (jnp.max(jnp.abs(x)) + jnp.float32(1e-9))


def make(spec: dict, seed: int, chips: int = 1) -> dict:
    import jax

    if spec.get("generator") != "integrated_noise":
        raise ValueError(f"unknown generator {spec.get('generator')!r}")
    shape = tuple(spec["shape"])
    base = jax.jit(_integrated_noise, static_argnums=1)(
        seed_key(int(spec["base_seed"])), shape)
    order, sign = variant(seed, shape)
    return {"field": jax.jit(lambda x: x.transpose(order) * sign)(base)}
