"""Greedy decode of a fixed batch, tensor-parallel over one host's chips.

``serve_decode``'s path with the model and its caches on a mesh of every
chip the process holds, laid out by the program's sharding rules
(``sharding/rules.make_plan``): the weights are made shard by shard with
``plan.params_shardings`` (the values ``bench/weights.params`` gives),
the caches are placed by ``plan.cache_spec``, and the prefill and decode
steps run with ``plan.shard_fn()``. Set-up prefills ``prefill_batch``
prompts at a time and writes each part's caches into the batch's, so
that only the mesh's share of the whole cache lands on a chip. The
window, the restore between turns and the comparison that decides
``correct`` are ``serve_decode``'s.

The traffic's ``mesh`` gives the (data, model) shape the host must
have. Its work (``work``) is one chip's share of the step, and the
trace's collective instructions (``COLLECTIVES``) are counted as kernel
families with no work of their own.
"""

from __future__ import annotations

import functools
import importlib
import time

from bench import common, weights
from bench.model import model_config
from bench.paths import serve_decode
from repro.train.steps import StepConfig, make_decode_step, make_prefill_step

METRIC = serve_decode.METRIC

#: The instruction bases of the collectives a chip trace of this path
#: shows (TPU v5e, jax 0.9): XLA's all-reduces and all-gathers, and the
#: TPU compiler's asynchronous collective fusion (the q all-gather).
COLLECTIVES = ("all-gather", "all-reduce", "async-collective-start",
               "async-collective-done")


def _place(caches, part, row):
    """``caches`` with the prefilled ``part`` written at batch row
    ``row`` and position 0 (every leaf is stacked over layers first)."""
    import jax
    return jax.tree.map(
        lambda z, p: jax.lax.dynamic_update_slice(
            z, p.astype(z.dtype), (0, row) + (0,) * (p.ndim - 2)),
        caches, part)


class Path(serve_decode.Path):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_host_mesh
        from repro.models.transformer import init_model
        from repro.sharding.rules import make_plan
        from repro.train.steps import init_caches

        t = ctx.traffic
        self.ctx = ctx
        self.cfg = cfg = model_config(ctx.config)
        self.batch, self.prompt, self.turn = t["batch"], t["prompt"], t["turn"]
        max_seq = self.prompt + self.turn
        mesh = make_host_mesh()
        if list(mesh.devices.shape) != list(t["mesh"]):
            raise ValueError(f"the cell asks for a {t['mesh']} mesh, the "
                             f"host makes {list(mesh.devices.shape)}")
        self.chips = mesh.devices.size
        plan = make_plan(mesh, cfg, ShapeSpec("serve", max_seq, self.batch,
                                              "decode"))
        shard = plan.shard_fn()
        rep = NamedSharding(mesh, P())

        dtype = jnp.dtype(ctx.config["param_dtype"])
        shapes = jax.eval_shape(lambda k: init_model(k, cfg, dtype),
                                jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(jax.jit(
            lambda: weights.params(ctx.seed, shapes),
            out_shardings=plan.params_shardings(shapes))())
        common.log(f"[run] weights made in {time.perf_counter() - t0:.3f} "
                   f"s over mesh {dict(mesh.shape)}")
        self.prompts = jax.jit(
            lambda: weights.tokens(ctx.seed, (self.batch, self.prompt),
                                   cfg.vocab_size), out_shardings=rep)()

        empty = jax.eval_shape(lambda: init_caches(cfg, self.batch, max_seq))
        kv = NamedSharding(mesh, plan.cache_spec("kv"))
        shardings = empty._replace(
            k=kv, v=kv, length=NamedSharding(mesh, plan.cache_spec("kv_len")))
        caches = jax.jit(lambda: init_caches(cfg, self.batch, max_seq),
                         out_shardings=shardings)()
        prefill = jax.jit(make_prefill_step(
            cfg, StepConfig(remat=False, use_flash=t["prefill_flash"]),
            shard))
        place = jax.jit(_place, donate_argnums=(0,), out_shardings=shardings)
        pb, first = t["prefill_batch"], []
        for i in range(0, self.batch, pb):
            logits, part = prefill(self.params,
                                   {"tokens": self.prompts[i:i + pb]})
            caches = place(caches, part, jnp.int32(i))
            first.append(jnp.argmax(logits, -1).astype(jnp.int32))
            del part
        self.caches = caches
        self.first = jax.device_put(jnp.concatenate(first)[:, None], rep)
        self.restore = jax.jit(
            functools.partial(serve_decode._restore, prompt=self.prompt),
            donate_argnums=(0,), out_shardings=shardings)
        decode = make_decode_step(cfg, StepConfig(remat=False), shard)

        def step(params, tok, caches):
            out, caches = decode(params, {"tokens": tok}, caches)
            return jnp.argmax(out, -1).astype(jnp.int32)[:, None], caches

        self.step = jax.jit(step, donate_argnums=(2,),
                            out_shardings=(rep, shardings))
        self.pos = 0
        self.tokens: list = []
        self.whole_turn: list | None = None
        # warm up: a restore and two steps compile everything the window
        # runs; the next issue starts a fresh turn
        for _ in range(2):
            self.issue()
        jax.block_until_ready(self.tok)
        self.pos, self.whole_turn = 0, None

    def work(self) -> dict:
        fam = importlib.import_module(f"bench.work.{self.ctx.config['work']}")
        # the least context of the turn: its first step attends prompt + 1
        return {"step": fam.decode(self.ctx.config["model"], self.batch,
                                   self.prompt + 1, self.chips),
                "kernels": {name: [] for name in COLLECTIVES}}


def setup(ctx):
    return Path(ctx)
