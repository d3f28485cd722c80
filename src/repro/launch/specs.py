"""Input ShapeDtypeStruct specs + lowering targets per (arch x input shape).

``input_specs`` returns weak-type-correct, shardable stand-ins — never
allocating device memory — for every model input, including the stub
modality frontends: VLM patch embeddings and audio codec tokens arrive as
precomputed structs of the right shape (the one sanctioned carve-out).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.precision import BF16, MIXED_TRAIN
from repro.models import transformer as T
from repro.sharding import partition as SH
from repro.training import optimizer as OPT
from repro.training.train_loop import make_train_step

INPUT_SHAPES = {
    "train_4k": {"kind": "train", "seq": 4096, "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32768, "batch": 32},
    "decode_32k": {"kind": "decode", "seq": 32768, "batch": 128},
    "long_500k": {"kind": "decode", "seq": 524288, "batch": 1},
}

# archs whose optimizer moments are bf16 in the dry-run (memory; see docs)
LOW_MEM_OPT_THRESHOLD = 1e11


@dataclass
class LoweringTarget:
    """A function + fully-sharded arg structs, ready to .lower()."""
    fn: Callable
    args: tuple
    donate_argnums: tuple = ()
    static_meta: dict = None


def _sds(shape, dtype, mesh=None, spec=None):
    sharding = NamedSharding(mesh, spec) if mesh is not None else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def params_struct(cfg: ModelConfig, mesh, policy, fsdp: bool):
    struct = jax.eval_shape(
        functools.partial(T.init_params, jax.random.PRNGKey(0), cfg,
                          policy=policy))
    specs = SH.param_pspecs(struct, cfg, fsdp=fsdp, mesh=mesh)
    return SH.with_sharding(struct, specs, mesh), specs


def use_fsdp(cfg: ModelConfig) -> bool:
    return cfg.param_counts()["total"] > 2e10


def input_specs(cfg: ModelConfig, shape_name: str, mesh):
    """Returns the batch-input structs for the given input shape."""
    info = INPUT_SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    bspec1 = SH.batch_pspec(mesh, B, extra_dims=1)
    kind = info["kind"]

    def tok_struct(seq):
        if cfg.num_codebooks:
            return _sds((B, seq, cfg.num_codebooks), jnp.int32, mesh,
                        SH.batch_pspec(mesh, B, extra_dims=2))
        return _sds((B, seq), jnp.int32, mesh, bspec1)

    if kind == "train":
        text_S = S - cfg.num_prefix_embeds
        batch = {"tokens": tok_struct(text_S),
                 "labels": tok_struct(text_S),
                 "loss_mask": _sds((B, text_S), jnp.float32, mesh, bspec1)}
        if cfg.num_prefix_embeds:
            batch["prefix_embeds"] = _sds(
                (B, cfg.num_prefix_embeds, cfg.d_model), jnp.bfloat16, mesh,
                SH.batch_pspec(mesh, B, extra_dims=2))
        return batch
    if kind == "prefill":
        return {"tokens": tok_struct(S),
                "lengths": _sds((B,), jnp.int32, mesh, P())}
    # decode: one new token against a cache of S
    return {"tokens": tok_struct(1),
            "lengths": _sds((B,), jnp.int32, mesh, P())}


def cache_specs(cfg: ModelConfig, B: int, max_len: int, mesh,
                dtype=jnp.bfloat16):
    struct = T.cache_struct(cfg, B, max_len, dtype)
    specs = SH.cache_pspecs(struct, mesh, B)
    return SH.with_sharding(struct, specs, mesh)


def make_target(cfg: ModelConfig, shape_name: str, mesh,
                fsdp: Optional[bool] = None) -> LoweringTarget:
    """Build the (fn, sharded arg structs) pair to lower for one combo."""
    info = INPUT_SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    kind = info["kind"]
    fsdp = use_fsdp(cfg) if fsdp is None else fsdp
    meta = {"arch": cfg.name, "shape": shape_name, "kind": kind,
            "batch": B, "seq": S, "fsdp": fsdp}

    if kind == "train":
        from repro import perf_flags
        low_mem = cfg.param_counts()["total"] > LOW_MEM_OPT_THRESHOLD
        policy = MIXED_TRAIN
        if low_mem and perf_flags.flag("bf16_params"):
            # §Perf target B: bf16 parameter storage for >100B archs
            from repro.core.precision import Policy
            policy = Policy(jnp.bfloat16, jnp.bfloat16, jnp.float32)
        factored = low_mem and perf_flags.flag("factored_opt")
        accum = int(perf_flags.flag_value("grad_accum", "1")) \
            if low_mem else 1

        pstruct, pspecs = params_struct(cfg, mesh, policy, fsdp)
        mdt = jnp.bfloat16 if low_mem else jnp.float32
        ostruct = jax.eval_shape(
            functools.partial(OPT.init_state, moment_dtype=mdt,
                              factored=factored), pstruct)
        if factored:
            ospecs = OPT.AdamWState(
                step=P(), mu=None,
                nu=OPT.factored_nu_pspecs(pspecs, pstruct))
        else:
            ospecs = OPT.AdamWState(step=P(),
                                    mu=jax.tree.map(lambda s: s, pspecs),
                                    nu=jax.tree.map(lambda s: s, pspecs))
        ostruct = SH.with_sharding(ostruct, ospecs, mesh)
        batch = input_specs(cfg, shape_name, mesh)
        opt_cfg = OPT.AdamWConfig(factored=factored)
        step = make_train_step(cfg, opt_cfg, policy=policy, remat=True,
                               grad_accum=accum)
        meta.update(low_mem_opt=low_mem, factored=factored,
                    grad_accum=accum, perf_opts=perf_flags.active())
        return LoweringTarget(fn=step, args=(pstruct, ostruct, batch),
                              donate_argnums=(0, 1), static_meta=meta)

    policy = BF16
    pstruct, _ = params_struct(cfg, mesh, policy, fsdp)
    max_len = S
    cstruct = cache_specs(cfg, B, max_len, mesh, policy.compute_dtype)

    if kind == "prefill":
        ins = input_specs(cfg, shape_name, mesh)

        def prefill_fn(params, tokens, lengths, cache):
            return T.forward_prefill(params, cfg, tokens, lengths, cache,
                                     policy=policy, max_len=max_len,
                                     last_only=True)[:2]

        return LoweringTarget(
            fn=prefill_fn,
            args=(pstruct, ins["tokens"], ins["lengths"], cstruct),
            donate_argnums=(3,), static_meta=meta)

    ins = input_specs(cfg, shape_name, mesh)

    def decode_fn(params, tokens, cache, lengths):
        return T.forward_decode(params, cfg, tokens, cache, lengths,
                                policy=policy, max_len=max_len)[:2]

    return LoweringTarget(
        fn=decode_fn, args=(pstruct, ins["tokens"], cstruct, ins["lengths"]),
        donate_argnums=(2,), static_meta=meta)
