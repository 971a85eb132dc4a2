"""Sharding rules: logical-axis -> mesh-axis resolution with divisibility
fallbacks (the production pattern: Megatron/MaxText-style logical rules, but
resolved per-architecture at mesh-build time).

Mesh axes:
  pod    (multi-pod only) — outermost data-parallel hop (DCI links)
  data   — FSDP: parameters/optimizer sharded, all-gathered per layer;
           batch (and long-sequence) dimension of activations
  model  — TP: attention heads / FFN hidden / vocab; EP: MoE experts

Strategy per tensor class (see DESIGN.md §Sharding rules):
  * dense kernels (d_in, d_out): P("data", "model") — FSDP x TP
  * attention projections: TP over heads when divisible; K/V projections
    with fewer KV heads than the model axis are replicated over it
    (P("data", None)): every model shard computes the few K/V heads
  * KV cache: heads on the model axis when divisible, else its sequence
    axis; decode then attends each device's positions for every query
    head and combines the parts (logical "kv_q", "kv_split")
  * MoE experts (E, d, f): EP P("model", "data", None) when E % model == 0,
    else TP inside experts P(None, "data", "model")
  * embeddings (V, d): P("model", "data") — vocab-sharded
  * activations (B, L, D): P(("pod","data"), None, None); batch=1
    long-context shards the sequence axis instead: P(None, ("pod","data"), None)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec


@dataclasses.dataclass
class ShardingPlan:
    mesh: Mesh
    cfg: ModelConfig
    shape: ShapeSpec
    data_axes: tuple[str, ...]      # ("pod","data") or ("data",)
    model_axis: str
    shard_seq: bool                 # batch too small -> shard sequence
    attn_tp: bool                   # heads divisible by model axis
    kv_tp: bool                     # kv heads divisible
    moe_ep: bool

    # ---- parameter specs ---------------------------------------------------
    def param_spec(self, path: tuple[str, ...], leaf: Any) -> P:
        """Spec for one parameter leaf. Layer-stacked subtrees (scan-over-
        layers: 'blocks', 'enc_blocks', 'tail') carry a leading layer axis
        that is never sharded — the logical rule applies to the remaining
        dims."""
        name = "/".join(str(p) for p in path)
        nd = leaf.ndim if hasattr(leaf, "ndim") else 0
        model = self.model_axis
        data = "data"
        stacked = any(seg in name for seg in ("blocks", "tail/"))
        if "tail" in name.split("/"):
            stacked = True
        if "shared_attn" in name:
            stacked = False
        end = nd - (1 if stacked else 0)   # effective (logical) rank

        def wrap(*spec_dims):
            return P(None, *spec_dims) if stacked else P(*spec_dims)

        if end <= 1:
            return P()
        # embeddings
        if "embed" in name and "table" in name:
            return P(model, data)
        # MoE expert banks (E, d_in, d_out)
        if ("experts" in name or "shared/" in name or
                name.endswith("shared")) and end == 3:
            if self.moe_ep and "experts" in name:
                return wrap(model, data, None)
            return wrap(None, data, model)
        if "router" in name:
            return wrap(data, None)
        # attention projections
        if "wq" in name:
            return wrap(data, model) if self.attn_tp \
                else wrap((data, model), None)
        if any(k in name for k in ("wk", "wv")):
            return wrap(data, model) if self.kv_tp else wrap(data, None)
        if "wo" in name:
            return wrap(model, data) if self.attn_tp \
                else wrap((data, model), None)
        # MLP
        if any(k in name for k in ("up", "gate")) and end == 2:
            return wrap(data, model) if self._ff_tp() \
                else wrap((data, model), None)
        if "down" in name and end == 2:
            return wrap(model, data) if self._ff_tp() \
                else wrap((data, model), None)
        # SSM projections
        if "in_proj" in name:
            return wrap(data, None)     # split boundaries misalign with TP
        if "out_proj" in name:
            return wrap(model, data) if self._ssm_tp() \
                else wrap((data, model), None)
        if "conv_w" in name:
            return wrap(None, None)
        if end == 2:
            return wrap(data, None)
        return P()

    def _ff_tp(self) -> bool:
        ms = self.mesh.shape[self.model_axis]
        ff = self.cfg.moe_d_ff or self.cfg.d_ff
        return ff % ms == 0 if ff else False

    def _ssm_tp(self) -> bool:
        # shard the SSD head dimension (d_inner) across model axis
        ms = self.mesh.shape[self.model_axis]
        d_inner = self.cfg.ssm_expand * self.cfg.d_model
        n_heads = d_inner // max(self.cfg.ssm_head_dim, 1)
        return n_heads % ms == 0 if n_heads else False

    def params_shardings(self, params_shape) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(
                self.mesh, self.param_spec(
                    tuple(getattr(p, "key", getattr(p, "idx", p))
                          for p in path), leaf)),
            params_shape)

    # ---- activation / batch specs ------------------------------------------
    def batch_spec(self) -> P:
        if self.shard_seq:
            return P(None, self.data_axes)
        return P(self.data_axes, None)

    def act_spec(self, logical: str) -> P:
        data = self.data_axes
        model = self.model_axis
        batch = None if self.shard_seq else data
        seq = data if self.shard_seq else None
        return {
            "hidden": P(batch, seq, None),
            "logits": P(batch, seq, model),
            "ffn_hidden": P(batch, seq, model) if self._ff_tp()
            else P(batch, seq, None),
            "attn_q": P(batch, seq, model if self.attn_tp else None, None),
            "attn_out": P(batch, seq, model if self.attn_tp else None, None),
            "moe_expert_in": P(model if self.moe_ep else None, None, None),
            "moe_expert_out": P(model if self.moe_ep else None, None, None),
            "ssm_x": P(batch, seq, model if self._ssm_tp() else None, None),
            "kv_q": P(batch, seq, None, None),
            "kv_split": P(batch, self._kv_seq_axes()),
        }.get(logical, P())

    def _kv_seq_axes(self):
        """The mesh axes a KV cache's sequence axis is split over."""
        return self.cache_spec("kv")[2]

    def shard_fn(self):
        def fn(logical: str, x):
            try:
                spec = self.act_spec(logical)
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, spec))
            except (ValueError, KeyError):
                return x
        fn.kv_splits = self._kv_splits()
        fn.kv_local = self.kv_local
        return fn

    def _kv_splits(self) -> int:
        axes = self._kv_seq_axes()
        axes = () if axes is None else \
            (axes if isinstance(axes, tuple) else (axes,))
        return int(np.prod([self.mesh.shape[a] for a in axes]))

    def kv_local(self, write, buf, new, length, *extra):
        """``write(part, new, length - first, *extra)`` on each device's
        part of ``buf``, a KV cache leaf split on its sequence axis (one
        layer's, or stacked over layers), ``first`` being the part's first
        position: a write lands on the one device that holds its
        position, and no device sees another's part. ``extra`` is
        replicated."""
        spec = self.cache_spec("kv")
        spec = P(*spec[len(spec) - buf.ndim:])
        batch, seq, heads, minor = spec[-4:]
        part = buf.shape[-3] // self._kv_splits()

        def local(buf, new, length, *extra):
            first = jax.lax.axis_index(seq) * part
            return write(buf, new, length - first, *extra)

        return jax.shard_map(
            local, mesh=self.mesh, out_specs=spec,
            in_specs=(spec, P(batch, heads, minor), P(batch))
            + (P(),) * len(extra))(buf, new, length, *extra)

    # ---- KV cache / SSM state specs -----------------------------------------
    def cache_spec(self, kind: str) -> P:
        data = self.data_axes
        model = self.model_axis
        batch = None if self.shard_seq else data
        seq = data if self.shard_seq else None
        if kind == "kv":           # (layers, B, S, KV, hd)
            if self.kv_tp:
                return P(None, batch, seq, model, None)
            # kv heads not divisible: shard the cache's sequence axis on the
            # model axis instead of replicating 16x (HBM capacity!)
            if seq is None:
                return P(None, batch, model, None, None)
            return P(None, batch, seq, None, None)
        if kind == "kv_len":       # (layers, B)
            return P(None, batch)
        if kind == "ssm_h":        # (layers, B, H, P, N)
            return P(None, batch, model if self._ssm_tp() else None,
                     None, None)
        if kind == "ssm_conv":     # (layers, B, K-1, conv_dim)
            return P(None, batch, None, model if self._ssm_tp() else None)
        return P()


#: Mesh shard-choice names (kept string-identical to `core/mesh.py`'s
#: constants; asserted in tests/test_mesh.py so they cannot drift).
m_REPLICATE = "replicate"
m_SPLIT_N = "split_n"
m_SPLIT_K = "split_k"


def mesh_tp_choices(n_chips: int, *, out_channels: int, reduce_dim: int,
                    n_heads: int | None = None,
                    n_experts: int | None = None) -> tuple[str, ...]:
    """Valid CIM-mesh shard choices for one canonical layer, under the same
    divisibility discipline `make_plan` applies per tensor class — the
    mesh path (`core/mesh.py`) resolves its per-layer TP choices here so
    the JAX-side rules and the analytical mesh model can never disagree
    on when TP engages.

    Returned names (preference order): ``replicate`` (always — the
    fully-FSDP / replicated-compute fallback analog, the layer whole on
    one chip), ``split_n`` (TP over output channels — attention heads for
    qkv/o projections, FFN hidden for MLPs; the `attn_tp` rule) and
    ``split_k`` (TP over the reduction dim with a partial-sum all-reduce).

    Fallback semantics, mirroring `make_plan`:
      * ``n_heads`` given and ``n_heads % n_chips != 0`` → the `attn_tp`
        rule fails, both splits are withheld (splitting inside a head
        misaligns attention compute — the rules replicate instead of
        raising), leaving ``("replicate",)``.
      * ``n_experts`` given and ``n_experts % n_chips == 0`` → expert
        parallelism: whole expert GEMMs distribute across chips as
        replicated instances (the mesh placement layer spreads the
        ``count=E`` instances), so no intra-GEMM split is offered.
      * ``n_experts`` given and ``E % n_chips != 0`` → the `moe_ep` rule
        fails and falls back to TP *inside* each expert (the
        ``P(None, "data", "model")`` branch): splits by plain
        divisibility, ``replicate`` when neither divides.

    Pure arithmetic — no jax objects — so the mesh path can resolve
    choices without building a device mesh."""
    choices = [m_REPLICATE]
    if n_chips <= 1:
        return tuple(choices)
    if n_heads is not None and (n_heads <= 0 or n_heads % n_chips != 0):
        return tuple(choices)
    if n_experts is not None and n_experts > 0 and \
            n_experts % n_chips == 0:
        return tuple(choices)
    if out_channels % n_chips == 0 and out_channels >= n_chips:
        choices.append(m_SPLIT_N)
    if reduce_dim % n_chips == 0 and reduce_dim >= n_chips:
        choices.append(m_SPLIT_K)
    return tuple(choices)


def mesh_grad_choices(n_chips: int, *, out_channels: int,
                      reduce_dim: int) -> tuple[str, ...]:
    """Valid CIM-mesh shard choices for one weight-grad GEMM
    (`workload.OP_WGRAD`, canonical dims N=K_fwd, K=N_fwd, C=M tokens) —
    the FSDP side of the rules, mirroring the ``data`` axis strategy
    `make_plan` applies to parameters/optimizer state:

      * ``replicate`` — always valid: one chip computes the full gradient.
      * ``split_n`` — FSDP sharded gradients: each chip computes the 1/n
        slice of delta_W along the forward weight's output channels it
        owns (the P("data", ...) parameter shard), when divisible.
      * ``split_k`` — data parallelism: chips split the token reduction
        dim and ring-all-reduce fp32 partial gradients (the classic DP
        gradient sync; `mesh.shard_eval` prices the all-reduce at
        accumulator width), when divisible.

    No head/expert fallbacks: gradients have no attention-compute or
    routing alignment constraint — a grad shard never has to follow the
    head boundary the forward TP rule protects. Pure arithmetic, like
    `mesh_tp_choices`."""
    choices = [m_REPLICATE]
    if n_chips <= 1:
        return tuple(choices)
    if out_channels % n_chips == 0 and out_channels >= n_chips:
        choices.append(m_SPLIT_N)
    if reduce_dim % n_chips == 0 and reduce_dim >= n_chips:
        choices.append(m_SPLIT_K)
    return tuple(choices)


def make_plan(mesh: Mesh, cfg: ModelConfig, shape: ShapeSpec) -> ShardingPlan:
    axes = mesh.axis_names
    model_axis = "model"
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    ms = mesh.shape[model_axis]
    total_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    shard_seq = shape.global_batch < total_data
    attn_tp = cfg.n_heads % ms == 0 if cfg.n_heads else False
    kv_tp = cfg.n_kv_heads % ms == 0 if cfg.n_kv_heads else False
    moe_ep = (cfg.moe_sharding == "ep" or
              (cfg.moe_sharding == "auto" and cfg.n_experts % ms == 0)) \
        and cfg.n_experts > 0 and cfg.n_experts % ms == 0
    return ShardingPlan(mesh=mesh, cfg=cfg, shape=shape,
                        data_axes=data_axes, model_axis=model_axis,
                        shard_seq=shard_seq, attn_tp=attn_tp, kv_tp=kv_tp,
                        moe_ep=moe_ep)
