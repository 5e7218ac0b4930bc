"""Train/serve step construction: model + sharding rules -> jit-able steps.

This is the single source of truth consumed by the trainer, the examples
and the multi-pod dry-run: the same `StepBundle` lowers on the production
mesh (ShapeDtypeStructs, no allocation) and executes on the reduced smoke
configs.

Train shapes run gradient accumulation over `plan.microbatches` (a scan,
so HLO size is O(1) in the count) -- the activation-memory lever that
fits the 104B config on 16 GiB chips.  Decode shapes lower `serve_step`
(one token against a seq_len-deep cache).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.api import Model, build_model
from repro.models import transformer as tr
from repro.models.layers import shapes_tree
from repro.optim import adamw_init, adamw_update, warmup_cosine
from repro.sharding.rules import MeshRules


# ---------------------------------------------------------------------------
# batch sharding: widest prefix of the data axes that divides the batch
# ---------------------------------------------------------------------------


def batch_axes_for(rules: MeshRules, batch_size: int):
    axes = rules.data_axes
    while axes:
        size = 1
        for a in axes:
            size *= rules.mesh.shape[a]
        if batch_size % size == 0:
            return axes
        axes = axes[:-1]
    return ()


def batch_pspec(rules: MeshRules, batch_size: int, ndim: int) -> P:
    axes = batch_axes_for(rules, batch_size)
    spec = [None] * ndim
    if axes:
        spec[0] = axes if len(axes) > 1 else axes[0]
    return P(*spec)


# ---------------------------------------------------------------------------
# Cache sharding (decode shapes)
# ---------------------------------------------------------------------------


def cache_pspecs(cfg: ArchConfig, rules: MeshRules, cache_specs: dict,
                 batch: int) -> dict:
    """PartitionSpecs for the decode cache pytree."""
    baxes = batch_axes_for(rules, batch)
    b_entry = (baxes if len(baxes) > 1 else (baxes[0] if baxes else None))
    tp = rules.tp_axis
    tp_size = rules.mesh.shape[tp] if tp else 1

    def kv_spec(s) -> P:
        # (L, B, S, Hkv, hd) or (chunks, B, S, Hkv, hd)
        _, b, sc, hkv, _ = s.shape
        mode = cfg.plan.decode_kv_shard
        if tp and mode in ("heads", "auto") and hkv % tp_size == 0:
            return P(None, b_entry, None, tp, None)
        if tp and mode in ("seq", "auto") and sc % tp_size == 0:
            return P(None, b_entry, tp, None, None)
        return P(None, b_entry, None, None, None)

    out = {}
    for k, s in cache_specs.items():
        if k in ("k", "v", "xk", "xv"):
            out[k] = kv_spec(s)
        elif k == "ssm":      # (L, B, nh, hd, ds)
            nh = s.shape[2]
            out[k] = P(None, b_entry,
                       tp if (tp and nh % tp_size == 0) else None, None, None)
        elif k == "conv":     # (L, B, W-1, C)
            c = s.shape[3]
            out[k] = P(None, b_entry, None,
                       tp if (tp and c % tp_size == 0) else None)
        elif k == "pos_buf":
            out[k] = P(None)
        else:                 # cur and misc scalars
            out[k] = P()
    return out


# ---------------------------------------------------------------------------
# Bundle
# ---------------------------------------------------------------------------


@dataclass
class StepBundle:
    """Everything needed to lower/run one (arch x shape) cell on a mesh."""

    cfg: ArchConfig
    shape: ShapeConfig
    mesh: Mesh
    rules: MeshRules
    model: Model
    kind: str                     # "train" | "prefill" | "decode"
    step_fn: Callable             # jit-able python callable
    in_shardings: tuple
    out_shardings: Any
    abstract_args: tuple          # ShapeDtypeStructs matching step_fn args
    donate_argnums: tuple = ()

    def jitted(self):
        return jax.jit(
            self.step_fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )

    def lower(self):
        with self.mesh:
            return self.jitted().lower(*self.abstract_args)


def _named(rules: MeshRules, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def param_pspecs(model: Model, rules: MeshRules):
    from repro.models.layers import ParamSpec

    return jax.tree.map(
        lambda s: rules.param(s.axes, s.shape), model.specs(),
        is_leaf=lambda x: isinstance(x, ParamSpec))


def opt_pspecs(model: Model, rules: MeshRules):
    from repro.models.layers import ParamSpec
    from repro.optim.adamw import AdamWState

    moment = jax.tree.map(
        lambda s: rules.opt(s.axes, s.shape), model.specs(),
        is_leaf=lambda x: isinstance(x, ParamSpec))
    return AdamWState(step=P(), mu=moment,
                      nu=jax.tree.map(lambda x: x, moment))


def batch_pspecs_for_shape(model: Model, rules: MeshRules,
                           shape: ShapeConfig) -> dict:
    specs = model.input_specs(shape)
    return {k: batch_pspec(rules, v.shape[0], len(v.shape))
            for k, v in specs.items()}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(model: Model, *, lr_kw: Optional[dict] = None,
                    microbatches: int = 1):
    lr_kw = lr_kw or dict(peak_lr=3e-4, warmup_steps=100, total_steps=10_000)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch, step):
        if microbatches > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])

            mb = jax.tree.map(split, batch)

            def acc(carry, mbatch):
                (l, g, m) = carry
                (li, mi), gi = grad_fn(params, mbatch)
                g = jax.tree.map(jnp.add, g, gi)
                m = jax.tree.map(jnp.add, m, mi)
                return (l + li, g, m), None

            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads, metrics), _ = jax.lax.scan(
                acc, (jnp.float32(0.0), zeros_g,
                      {"ce": jnp.float32(0), "zloss": jnp.float32(0),
                       "aux": jnp.float32(0)}
                      if model.cfg.family != "encdec"
                      else {"ce": jnp.float32(0)}),
                mb)
            inv = 1.0 / microbatches
            loss = loss * inv
            grads = jax.tree.map(lambda g: g * inv, grads)
            metrics = jax.tree.map(lambda m: m * inv, metrics)
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        lr = warmup_cosine(step, **lr_kw)
        params, opt_state, opt_metrics = adamw_update(
            grads, opt_state, params, lr=lr)
        return params, opt_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


# ---------------------------------------------------------------------------
# Compressed-gradient train step (beyond-paper: int8 EF on the DP axis)
# ---------------------------------------------------------------------------


def make_compressed_train_step(model: Model, rules: MeshRules,
                               *, lr_kw: Optional[dict] = None):
    """dp_only variant with an EXPLICIT int8 all-reduce on the data axes.

    shard_map exposes the gradient synchronisation that pjit normally
    fuses, so error-feedback int8 compression (repro.optim.compress) can
    quantise the wire payload: 4x fewer collective bytes on the DP
    all-reduce.  The EF residual lives per-device (leading device axis).
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim.compress import ef_compress

    assert rules.plan.mode == "dp_only", "compression targets the DP plan"
    lr_kw = lr_kw or dict(peak_lr=3e-4, warmup_steps=100, total_steps=10_000)
    axes = rules.data_axes
    mesh = rules.mesh

    def loss_fn(params, batch):
        return model.loss(params, batch)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def sync_block(params, batch, residual):
        """Runs per device group: local grads -> EF int8 -> int32 psum."""
        (loss, metrics), grads = grad_fn(params, batch)
        res_local = jax.tree.map(lambda r: r[0], residual)
        from repro.optim.compress import CompressionState

        q, s, new_state = ef_compress(grads, CompressionState(res_local))

        def reduce_one(qv, sv):
            s_sh = jax.lax.pmax(sv, axes)
            v = qv.astype(jnp.float32) * sv
            q2 = jnp.clip(jnp.round(v / s_sh), -127, 127).astype(jnp.int32)
            total = jax.lax.psum(q2, axes)
            return total.astype(jnp.float32) * s_sh

        summed = jax.tree.map(reduce_one, q, s)
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        grads = jax.tree.map(lambda g: g / n_dev, summed)
        loss = jax.lax.pmean(loss, axes)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axes), metrics)
        new_res = jax.tree.map(lambda r: r[None], new_state.residual)
        return loss, metrics, grads, new_res

    batch_entry = axes if len(axes) > 1 else axes[0]
    p_spec = jax.tree.map(lambda _: P(), model.abstract_params())
    res_spec = jax.tree.map(lambda _: P(batch_entry), model.abstract_params())

    def train_step(params, opt_state, residual, batch, step):
        b_spec = jax.tree.map(lambda _: P(batch_entry), batch)
        loss, metrics, grads, new_res = jax.shard_map(
            sync_block, mesh=mesh,
            in_specs=(p_spec, b_spec, res_spec),
            out_specs=(P(), jax.tree.map(lambda _: P(), metrics_spec(model)),
                       p_spec, res_spec),
            check_vma=False,
        )(params, batch, residual)
        lr = warmup_cosine(step, **lr_kw)
        params, opt_state, opt_metrics = adamw_update(
            grads, opt_state, params, lr=lr)
        return params, opt_state, new_res, {
            "loss": loss, **metrics, **opt_metrics}

    return train_step


def metrics_spec(model: Model):
    if model.cfg.family == "encdec":
        return {"ce": P()}
    return {"ce": P(), "zloss": P(), "aux": P()}


def init_residual(model: Model, rules: MeshRules):
    """Per-device EF residual pytree (leading device axis, sharded)."""
    axes = rules.data_axes
    n_dev = 1
    for a in axes:
        n_dev *= rules.mesh.shape[a]
    return jax.tree.map(
        lambda s: jnp.zeros((n_dev,) + s.shape, jnp.float32),
        model.abstract_params())


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        # serving returns the last-position logits (next-token distribution);
        # last_only slices before the unembed (no (B, S, V) materialisation).
        logits = model.forward(params, batch, last_only=True)
        return logits[:, -1, :]

    return prefill_step


def make_decode_step(model: Model):
    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_step


# ---------------------------------------------------------------------------
# Bundle builder (the dry-run/trainer entry point)
# ---------------------------------------------------------------------------


def build_step_bundle(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                      *, unroll: bool = False, compressed: bool = False,
                      lr_kw: Optional[dict] = None,
                      model_kw: Optional[dict] = None) -> StepBundle:
    model = build_model(cfg, unroll=unroll, **(model_kw or {}))
    rules = MeshRules(cfg.plan, mesh)

    p_pspec = param_pspecs(model, rules)
    p_shard = _named(rules, p_pspec)
    abstract_params = model.abstract_params()

    if compressed and shape.kind == "train":
        from jax.sharding import PartitionSpec as P_

        from repro.optim.adamw import AdamWState

        step_fn = make_compressed_train_step(model, rules, lr_kw=lr_kw)
        o_pspec = opt_pspecs(model, rules)
        o_shard = _named(rules, o_pspec)
        b_pspec = batch_pspecs_for_shape(model, rules, shape)
        b_shard = _named(rules, b_pspec)
        axes = rules.data_axes
        entry = axes if len(axes) > 1 else axes[0]
        r_shard = _named(rules, jax.tree.map(
            lambda _: P_(entry), abstract_params))
        n_dev = 1
        for a in axes:
            n_dev *= mesh.shape[a]
        abstract_res = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_dev,) + s.shape, jnp.float32),
            abstract_params)
        abstract_opt = AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                abstract_params),
            nu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                abstract_params),
        )
        return StepBundle(
            cfg=cfg, shape=shape, mesh=mesh, rules=rules, model=model,
            kind="train", step_fn=step_fn,
            in_shardings=(p_shard, o_shard, r_shard, b_shard, None),
            out_shardings=(p_shard, o_shard, r_shard, None),
            abstract_args=(abstract_params, abstract_opt, abstract_res,
                           model.input_specs(shape),
                           jax.ShapeDtypeStruct((), jnp.int32)),
            donate_argnums=(0, 1, 2),
        )

    if shape.kind in ("train",):
        o_pspec = opt_pspecs(model, rules)
        o_shard = _named(rules, o_pspec)
        b_pspec = batch_pspecs_for_shape(model, rules, shape)
        b_shard = _named(rules, b_pspec)
        step_fn = make_train_step(
            model, lr_kw=lr_kw, microbatches=cfg.plan.microbatches)

        from repro.optim.adamw import AdamWState

        abstract_opt = AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                abstract_params),
            nu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                abstract_params),
        )
        abstract_batch = model.input_specs(shape)
        abstract_step = jax.ShapeDtypeStruct((), jnp.int32)
        return StepBundle(
            cfg=cfg, shape=shape, mesh=mesh, rules=rules, model=model,
            kind="train", step_fn=step_fn,
            in_shardings=(p_shard, o_shard, b_shard, None),
            out_shardings=(p_shard, o_shard, None),
            abstract_args=(abstract_params, abstract_opt, abstract_batch,
                           abstract_step),
            donate_argnums=(0, 1),
        )

    if shape.kind == "prefill":
        b_pspec = batch_pspecs_for_shape(model, rules, shape)
        b_shard = _named(rules, b_pspec)
        step_fn = make_prefill_step(model)
        abstract_batch = model.input_specs(shape)
        return StepBundle(
            cfg=cfg, shape=shape, mesh=mesh, rules=rules, model=model,
            kind="prefill", step_fn=step_fn,
            in_shardings=(p_shard, b_shard),
            out_shardings=None,
            abstract_args=(abstract_params, abstract_batch),
        )

    # decode: one new token with a seq_len-deep cache
    b = shape.global_batch
    cache_specs = model.cache_specs(b, shape.seq_len)
    c_pspec = cache_pspecs(cfg, rules, cache_specs, b)
    c_shard = _named(rules, c_pspec)
    tok_shard = NamedSharding(rules.mesh, batch_pspec(rules, b, 1))
    step_fn = make_decode_step(model)
    abstract_tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    return StepBundle(
        cfg=cfg, shape=shape, mesh=mesh, rules=rules, model=model,
        kind="decode", step_fn=step_fn,
        in_shardings=(p_shard, c_shard, tok_shard),
        out_shardings=(tok_shard, c_shard),
        abstract_args=(abstract_params, cache_specs, abstract_tokens),
        donate_argnums=(1,),
    )
