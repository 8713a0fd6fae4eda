"""Step builders: assemble jit-able train / prefill / decode steps with
full sharding annotations for a given (arch config, mesh, shape cell).

Every builder returns a StepBundle carrying the function, the abstract
arguments (ShapeDtypeStruct — no allocation), and in/out shardings, so
the dry-run can ``jit(fn, ...).lower(*abstract).compile()`` and the
trainers can feed real arrays through the same object.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.shapes import ShapeSpec, input_specs
from repro.models import encdec, lm
from repro.models.config import ArchConfig
from repro.optim import adamw
from repro.parallel.sharding import (ShardingRules, make_rules,
                                     params_shardings, use_rules)

Tree = Any


@dataclass
class StepBundle:
    name: str
    fn: Callable
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()
    rules: ShardingRules | None = None
    statics: dict = field(default_factory=dict)

    def jit(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self.jit().lower(*self.abstract_args)


def _model_mod(cfg: ArchConfig):
    return encdec if cfg.is_encdec else lm


def _batch_shardings(cfg: ArchConfig, shape: ShapeSpec,
                     rules: ShardingRules) -> dict[str, NamedSharding]:
    specs = input_specs(cfg, shape)
    out = {}
    for name, sds in specs.items():
        axes: tuple = ("batch",) + (None,) * (len(sds.shape) - 1)
        out[name] = rules.sharding_for(axes, sds.shape)
    return out


def abstract_state(cfg: ArchConfig, mesh: Mesh, opt: adamw.OptConfig | None
                   ) -> dict:
    """Abstract params/opt-state + their shardings for one arch."""
    rules = make_rules(cfg, mesh)
    model = _model_mod(cfg)
    aparams, specs = model.abstract_init(cfg)
    p_sh = params_shardings(rules, aparams, specs)
    out = {"rules": rules, "params": aparams, "param_specs": specs,
           "param_shardings": p_sh}
    if opt is not None:
        aopt = jax.eval_shape(
            functools.partial(adamw.init_state, cfg=opt), aparams)
        opt_specs = adamw.state_specs(specs)
        # ZeRO-1: moments additionally shard their "embed" axis over data
        # even when params are not FSDP-sharded
        zrules = make_rules(cfg, mesh)
        if "data" in mesh.shape:
            zrules.rules["embed"] = "data"
        o_sh = {"m": params_shardings(zrules, aopt["m"], opt_specs["m"]),
                "v": params_shardings(zrules, aopt["v"], opt_specs["v"]),
                "step": NamedSharding(mesh, P())}
        out |= {"opt": aopt, "opt_shardings": o_sh}
    return out


# ---------------------------------------------------------------- train step

def make_train_step(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
                    opt: adamw.OptConfig | None = None) -> StepBundle:
    opt = opt or adamw.OptConfig(moment_dtype=cfg.moment_dtype)
    st = abstract_state(cfg, mesh, opt)
    rules = st["rules"]
    b_sh = _batch_shardings(cfg, shape, rules)
    specs = input_specs(cfg, shape)

    def _loss(p, b):
        if cfg.is_encdec:
            return encdec.loss_fn(cfg, p, b["frames"], b["tokens"],
                                  b["labels"])
        return lm.loss_fn(cfg, p, b["tokens"], b["labels"])

    def train_step(params, opt_state, batch):
        with use_rules(rules):
            loss, grads = jax.value_and_grad(_loss)(params, batch)
            params, opt_state, om = adamw.apply_updates(
                params, grads, opt_state, opt)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics

    return StepBundle(
        name=f"{cfg.name}:{shape.name}:train",
        fn=train_step,
        abstract_args=(st["params"], st["opt"], specs),
        in_shardings=(st["param_shardings"], st["opt_shardings"], b_sh),
        out_shardings=(st["param_shardings"], st["opt_shardings"], None),
        donate_argnums=(0, 1),
        rules=rules,
        statics={"opt": opt, "state": st},
    )


# -------------------------------------------------------------- prefill step

def make_prefill_step(cfg: ArchConfig, mesh: Mesh,
                      shape: ShapeSpec) -> StepBundle:
    st = abstract_state(cfg, mesh, None)
    rules = st["rules"]
    b_sh = _batch_shardings(cfg, shape, rules)
    specs = input_specs(cfg, shape)
    model = _model_mod(cfg)

    if cfg.is_encdec:
        def prefill_step(params, batch):
            with use_rules(rules):
                return encdec.prefill(cfg, params, batch["frames"],
                                      batch["tokens"])
    else:
        def prefill_step(params, batch):
            with use_rules(rules):
                return lm.prefill(cfg, params, batch["tokens"])

    cache_sh, _ = _cache_shardings(cfg, rules, shape.global_batch,
                                   shape.seq_len, enc_len=shape.seq_len)
    return StepBundle(
        name=f"{cfg.name}:{shape.name}:prefill",
        fn=prefill_step,
        abstract_args=(st["params"], specs),
        in_shardings=(st["param_shardings"], b_sh),
        out_shardings=(None, cache_sh),
        rules=rules,
        statics={"state": st},
    )


# --------------------------------------------------------------- decode step

def _cache_shardings(cfg: ArchConfig, rules: ShardingRules, batch: int,
                     max_len: int, enc_len: int = 0):
    """Cache shardings with sequence-parallel fallbacks.

    A KV cache wants (batch -> data, kv_heads -> model); when either is
    indivisible (kv_heads=8 on a 16-way model axis; batch=1 for
    long_500k) the *sequence* axis takes over the freed mesh axes —
    split-KV decode, the flash-decoding layout. Without this, a 32k
    decode cache replicates across the model axis (~32 GiB/chip on the
    GQA archs — observed before this fix).
    """
    dp = rules.axis_size(rules.rules.get("batch"))
    tp = rules.axis_size(rules.rules.get("kv_heads"))
    if cfg.is_encdec:
        acache = jax.eval_shape(
            lambda: encdec.init_cache(cfg, batch, max_len, enc_len))
        cspecs = encdec.cache_specs(cfg)
    else:
        acache = jax.eval_shape(lambda: lm.init_cache(cfg, batch, max_len))
        cspecs = lm.cache_specs(cfg)

    seq_axes: list[str] = []
    batch_bad = batch % max(dp, 1) != 0
    kv_bad = cfg.n_kv_heads > 0 and cfg.n_kv_heads % max(tp, 1) != 0
    if batch_bad and "data" in rules.mesh.shape:
        seq_axes.append("data")
    if kv_bad and "model" in rules.mesh.shape:
        seq_axes.append("model")
    seq_total = 1
    for a in seq_axes:
        seq_total *= rules.mesh.shape[a]
    if seq_axes and max_len % seq_total == 0:
        rules.rules["kv_seq"] = tuple(seq_axes)

        def respec(axes):
            axes = list(axes)
            if batch_bad:
                axes[1] = None
            if len(axes) == 5 and axes[2] == "kv_heads":
                if kv_bad:
                    axes[2] = None
                axes[3] = "kv_seq"
            return tuple(axes)

        cspecs = jax.tree.map(respec, cspecs,
                              is_leaf=lambda x: isinstance(x, tuple))
    elif batch_bad:
        def respec(axes):
            axes = list(axes)
            axes[1] = None
            return tuple(axes)

        cspecs = jax.tree.map(respec, cspecs,
                              is_leaf=lambda x: isinstance(x, tuple))
    return params_shardings(rules, acache, cspecs), acache


def make_decode_step(cfg: ArchConfig, mesh: Mesh,
                     shape: ShapeSpec) -> StepBundle:
    st = abstract_state(cfg, mesh, None)
    rules = st["rules"]
    B, S = shape.global_batch, shape.seq_len
    cache_sh, acache = _cache_shardings(cfg, rules, B, S, enc_len=S)
    model = _model_mod(cfg)

    def serve_step(params, cache, tokens, pos):
        with use_rules(rules):
            logits, cache = model.decode_step(cfg, params, cache,
                                              tokens, pos)
        return logits, cache

    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    tok_sh = rules.sharding_for(("batch", None), (B, 1))
    scalar_sh = NamedSharding(mesh, P())
    return StepBundle(
        name=f"{cfg.name}:{shape.name}:decode",
        fn=serve_step,
        abstract_args=(st["params"], acache, tok_sds, pos_sds),
        in_shardings=(st["param_shardings"], cache_sh, tok_sh, scalar_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
        rules=rules,
        statics={"state": st},
    )


def make_step(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec,
              opt: adamw.OptConfig | None = None) -> StepBundle:
    if shape.kind == "train":
        return make_train_step(cfg, mesh, shape, opt)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape)
    return make_decode_step(cfg, mesh, shape)
