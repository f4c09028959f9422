"""normal: every entry of an input panel standard normal, float32."""
import jax
import jax.numpy as jnp


def draw(key, shape, spec):
    return jax.random.normal(key, shape, jnp.float32)
