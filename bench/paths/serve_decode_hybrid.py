"""Greedy decode of a fixed batch through a hybrid model's decode step:
Mamba2 layers, whose SSM states every step overwrites, beside a KV cache.

``serve_decode``'s window, turns and comparison, with two changes of
set-up. The prompts are prefilled ``prefill_batch`` at a time, and each
part's states and caches are written into the batch's decode-sized ones
(as ``serve_decode_tp`` does), one part at a time, so that the prefill
never holds the whole batch's caches twice. And set-up keeps a device
copy of the prefilled SSM states: the restore at each turn writes that
copy back beside ``serve_decode``'s zeroing of the KV positions from
``prompt`` on, so it gives back the prefilled states and caches bit for
bit.
"""

from __future__ import annotations

import functools

from bench import weights
from bench.model import make_params, model_config
from bench.paths import serve_decode
from bench.paths.serve_decode_tp import _place
from repro.train.steps import (StepConfig, init_caches, make_decode_step,
                               make_prefill_step)

METRIC = serve_decode.METRIC


def _restore(caches, saved, prompt: int):
    """(the saved SSM states, the KV caches as the prefill left them)."""
    _, kv = caches
    return saved, serve_decode._restore(kv, prompt)


class Path(serve_decode.Path):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        t = ctx.traffic
        self.ctx = ctx
        self.cfg = cfg = model_config(ctx.config)
        self.batch, self.prompt, self.turn = t["batch"], t["prompt"], t["turn"]
        max_seq = self.prompt + self.turn
        self.params = make_params(ctx.config, ctx.seed)
        self.prompts = weights.tokens(ctx.seed, (self.batch, self.prompt),
                                      cfg.vocab_size)
        caches = jax.jit(lambda: init_caches(cfg, self.batch, max_seq))()
        prefill = jax.jit(make_prefill_step(
            cfg, StepConfig(remat=False, use_flash=t["prefill_flash"])))
        place = jax.jit(_place, donate_argnums=(0,))
        pb, first = t["prefill_batch"], []
        for i in range(0, self.batch, pb):
            logits, part = prefill(self.params,
                                   {"tokens": self.prompts[i:i + pb]})
            caches = place(caches, part, jnp.int32(i))
            first.append(jnp.argmax(logits, -1).astype(jnp.int32))
            del part
            # one part at a time: each prefill's outputs are held from
            # its dispatch, so running ahead would hold several at once
            jax.block_until_ready(caches)
        self.saved = jax.jit(lambda s: jax.tree.map(jnp.copy, s))(caches[0])
        self.caches = caches
        self.first = jnp.concatenate(first)[:, None]
        restore = jax.jit(functools.partial(_restore, prompt=self.prompt),
                          donate_argnums=(0,))
        self.restore = lambda c: restore(c, self.saved)
        decode = make_decode_step(cfg, StepConfig(remat=False))

        def step(params, tok, caches):
            out, caches = decode(params, {"tokens": tok}, caches)
            return jnp.argmax(out, -1).astype(jnp.int32)[:, None], caches

        self.step = jax.jit(step, donate_argnums=(2,))
        self.pos = 0
        self.tokens: list = []
        self.whole_turn: list | None = None
        # warm up: a restore and two steps compile everything the window
        # runs; the next issue starts a fresh turn
        for _ in range(2):
            self.issue()
        jax.block_until_ready(self.tok)
        self.pos, self.whole_turn = 0, None

    def free(self):
        super().free()
        self.saved = None


def setup(ctx):
    return Path(ctx)
