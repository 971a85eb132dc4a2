"""The model under test, built from a configuration file's ``model`` dict."""

from __future__ import annotations

import time

from bench import common, weights


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["model"])


def make_params(config: dict, seed: int):
    """The program's parameter layout, in ``config["param_dtype"]``,
    filled from ``seed`` on the device."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_model
    cfg = model_config(config)
    dtype = jnp.dtype(config["param_dtype"])
    shapes = jax.eval_shape(lambda k: init_model(k, cfg, dtype),
                            jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    params = jax.block_until_ready(weights.params(seed, shapes))
    common.log(f"[run] weights made in {time.perf_counter() - t0:.3f} s")
    return params


def batch_concat(trees):
    """Concatenate cache pytrees along their batch axis (axis 1: every
    cache leaf is stacked over layers first)."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda *ts: jnp.concatenate(ts, axis=1), *trees)
