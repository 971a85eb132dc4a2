"""Seeds, tokens and weights, all made on the device from ``--seed``.

The weights take the layout the program's ``init_model`` declares (read
abstractly, with ``jax.eval_shape``) and the dtypes it serves them in,
but their values come from here, in one jitted call, so the plain
reference can be given the same numbers without taking anything the
program made.
"""

from __future__ import annotations

import zlib

import numpy as np


def key32(seed: int, stream: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` that keeps every bit of
    ``seed`` (PRNGKey drops the bits above 32) and differs per stream."""
    words = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), stream])
    return int(words.generate_state(1)[0] & 0x7FFFFFFF)


def tokens(seed: int, shape: tuple, vocab: int, stream: int = 1):
    """Uniform token ids in ``[0, vocab)``, made on the device."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(key32(seed, stream))
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab,
                                                jnp.int32))(key)


def _leaf(key, name: str, shape, dtype):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if name == "scale":                     # RMS norm scales, f32
        return (1.0 + 0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if name == "w":
        return (jax.random.normal(key, shape, dtype)
                / np.sqrt(shape[-2])).astype(dtype)
    # embedding tables and the rest
    return (0.02 * jax.random.normal(key, shape, dtype)).astype(dtype)


def params(seed: int, shapes):
    """A pytree shaped and typed as ``shapes`` (``jax.eval_shape`` of the
    program's initialiser), filled from ``seed`` in one jitted call."""
    import jax

    paths = jax.tree_util.tree_flatten_with_path(shapes)
    treedef = paths[1]
    leaves = paths[0]

    def fill(key):
        out = []
        for path, sd in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            last = getattr(path[-1], "key", str(path[-1]))
            out.append(_leaf(k, last, sd.shape, sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill)(jax.random.PRNGKey(key32(seed, 2)))
