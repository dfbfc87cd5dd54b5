"""What the plain references share: the matrix product at a stated operand
precision, LayerNorm, seeded leaves, and a hashable view of a configuration.
Straight ``jax.numpy``; nothing of the program."""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b, spec, dtype):
    """``einsum`` in float32 at ``highest``; with ``dtype`` the operands are
    first rounded to that type and multiplied exactly, which is what a unit
    with that operand type and float32 accumulation computes."""
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def seeded_leaves(shapes, std, seed):
    """Every leaf on the device in one jitted call from the seed: normal at
    ``std``; gains (names ending ``_g``) are 1 plus five times that."""
    def build(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape)
            out[name] = 1.0 + 5 * w if name.endswith('_g') else w
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def cfg_key(cfg):
    """The configuration's plain sizes as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))
