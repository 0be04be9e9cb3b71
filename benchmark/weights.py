"""The benchmark's own weights: one jitted call on the device, from the seed,
in float32 (the type the program trains and serves them in).

The program is asked only for the names and shapes of its parameters
(`jax.eval_shape` of its `init`); every value is made here, so that the plain
reference and the program start from weights neither of them made.  The rules
are the reference paper's own initialisation, read off a leaf's name:

- `*.g` (norm scale) = 1, every other 1-D leaf (biases) = 0;
- `embedding.*.w` ~ normal(0, 1);
- every other leaf ~ U(-1/sqrt(fan_in), 1/sqrt(fan_in)), fan_in = the product
  of all but the last (output) axis.
"""

import math

import jax
import jax.numpy as jnp


def _leaf(key, name, shape):
    if len(shape) == 1:
        fill = jnp.ones if name.endswith(".g") else jnp.zeros
        return fill(shape, jnp.float32)
    if name.startswith("embedding."):
        return jax.random.normal(key, shape, jnp.float32)
    bound = 1.0 / math.sqrt(math.prod(shape[:-1]))
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def make_params(shapes, seed):
    """``shapes``: {name: shape}.  Returns {name: float32 array} on the
    default device, the same values for the same seed."""
    names = sorted(shapes)

    def build(key):
        keys = jax.random.split(key, len(names))
        return {n: _leaf(k, n, tuple(shapes[n])) for n, k in zip(names, keys)}

    return jax.jit(build)(jax.random.key(int(seed) % (2 ** 63)))
