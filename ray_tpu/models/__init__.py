"""The model families, one module each, and the one way a replica draws a
family's parameters."""

import functools
import sys

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def normal(key, shape, scale=1.0, mean=0.0, dtype=jnp.float32):
    """`mean + scale x` cast to `dtype`, x standard normal float32 of `shape`,
    as ONE program: what a family's `init_params` draws a leaf with that is
    more than a bare normal, so that the leaf rounds the same way drawn
    eagerly and inside `draw_params` (apart, the normal's own
    `sqrt(2) erf_inv(u)` and the scaling are two programs and two roundings;
    together XLA folds the constants into one), and so that the bits fuse
    into the cast (no float32 copy of a stack)."""
    x = scale * jax.random.normal(key, shape, jnp.float32)
    return (x + mean if mean else x).astype(dtype)


def default_config():
    """The configuration served where none is given: the tiny Llama."""
    from ray_tpu.models import llama

    return llama.LlamaConfig.tiny()


@functools.partial(jax.jit, static_argnums=0)
def draw_params(config, key):
    """`init_params(config, key)` of the configuration's own module (imported:
    it defined the configuration's class) as ONE program a configuration.

    The configuration is static (every family's is a frozen dataclass) and the
    key an ARGUMENT, so one executable serves every seed: a second replica of
    the process draws with no trace, and a new process reads it from the
    persistent compile cache, where an eager draw is tens of one-primitive
    programs under the cache's one-second floor that compile at every start.
    The call returns before the device has drawn."""
    return sys.modules[type(config).__module__].init_params(config, key)
