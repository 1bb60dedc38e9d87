"""A serving decode cache: each tensor named, shaped and given a token
axis (or none) by the configuration's ``tensors`` table, for a batch of
``batch`` sequences at ``context`` tokens.

Values as in ``tree.py``: standard normal times ``scale`` from the
configuration's ``base_seed``, the run's seed picking each tensor's sign,
so every seed gives the codec the same work and the same payload sizes.
"""

from bench.inputs import tree


def _leaves(spec: dict) -> dict:
    return {"base_seed": spec["base_seed"],
            "leaves": [{"name": t["name"], "shape": t["shape"],
                        "init": "normal", "scale": t["scale"]}
                       for t in spec["tensors"]]}


def shapes(spec: dict, chips: int) -> dict:
    if chips != 1:
        raise ValueError("a decode cache is one chip's own sequences")
    for t in spec["tensors"]:
        ax = t["token_axis"]
        if ax is not None and t["shape"][ax] != spec["context"]:
            raise ValueError(f"{t['name']}: axis {ax} of {t['shape']} is "
                             f"not the context {spec['context']}")
    return tree.shapes(_leaves(spec), chips)


def make(spec: dict, seed: int, chips: int = 1) -> dict:
    return tree.make(_leaves(spec), seed, chips)
