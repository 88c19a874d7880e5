"""Random weights for a configuration, drawn on the device from the seed.

The benchmark makes the weights itself, in the layout the serving engine
reads, so the plain reference can use them without taking anything the
program made.  The model's code (``code/<code>.py``) gives the layout and
the scales as a table, ``leaf_table(m)``: name -> (shape, dtype, kind,
scale).  One jitted program draws every leaf with the hardware generator
(``rbg``) in float32, leaf i of the sorted names from ``fold_in(key, i)``,
and stores it in the type it is served in; the layout is checked against
the program's own abstract parameters.
"""
from __future__ import annotations

import math
import re

import numpy as np

SQRT3 = math.sqrt(3.0)
STAGE = re.compile(r"L(\d*)")


def _nest(flat: dict) -> dict:
    """Flat names -> the engine's tree: a name whose first part is ``L``
    goes to stages[0], ``L<k>`` to stages[k]; any other to the top."""
    out: dict = {"stages": []}
    for name, v in flat.items():
        parts = name.split("/")
        node = out
        stage = STAGE.fullmatch(parts[0])
        if stage:
            k = int(stage.group(1) or 0)
            out["stages"] += [{} for _ in range(k + 1 - len(out["stages"]))]
            node, parts = out["stages"][k], parts[1:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def seed_key(seed: int):
    """A 32-bit key from any whole number, 2**31 and beyond included."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word, impl="rbg")


def draw(table: dict, seed: int):
    """The weight tree of ``table`` (a model code's ``leaf_table``), drawn
    in one jitted program on the default device."""
    import jax
    import jax.numpy as jnp
    dtypes = {"bf16": jnp.bfloat16, "f32": jnp.float32}

    def make(key):
        flat = {}
        for i, (name, (shape, dt, kind, scale)) in enumerate(
                sorted(table.items())):
            u = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                   jnp.float32, -1.0, 1.0)
            if kind == "gain":
                v = 1.0 + scale * u
            elif kind == "bias":
                v = scale * u
            else:       # uniform with standard deviation ``scale``
                v = (scale * SQRT3) * u
            flat[name] = v.astype(dtypes[dt])
        return _nest(flat)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes (``abstract`` from ``repro.models.abstract_params``)."""
    import jax
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), abstract)
    if got != want:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"benchmark {got}\nprogram   {want}")
