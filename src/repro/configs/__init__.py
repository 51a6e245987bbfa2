"""Assigned-architecture registry: ``get(name)`` full config,
``smoke(name)`` reduced same-family config, ``input_specs(name, shape)``
ShapeDtypeStruct stand-ins for every entry-point input.

Shape cells (assigned to every arch):

    train_4k      seq 4,096   global_batch 256   -> train_step
    prefill_32k   seq 32,768  global_batch 32    -> prefill
    decode_32k    seq 32,768  global_batch 128   -> serve_step (1 token)
    long_500k     seq 524,288 global_batch 1     -> serve_step (1 token)

``long_500k`` policy per DESIGN.md §Arch-applicability: SSM/hybrid archs
run natively; pure full-attention archs are *natively skipped* but run
here via the paper's static block sparsity (retained local+global KV
cache), recorded as a beyond-paper application.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelCfg
from repro.models.model import LM

ARCH_IDS = [
    "deepseek_v2_lite_16b",
    "qwen3_moe_30b_a3b",
    "internvl2_1b",
    "glm4_9b",
    "qwen2_1_5b",
    "gemma2_2b",
    "llama3_2_1b",
    "jamba_v0_1_52b",
    "mamba2_130m",
    "seamless_m4t_medium",
]

# canonical external ids (brief spelling) -> module name
ALIASES = {
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "internvl2-1b": "internvl2_1b",
    "glm4-9b": "glm4_9b",
    "qwen2-1.5b": "qwen2_1_5b",
    "gemma2-2b": "gemma2_2b",
    "llama3.2-1b": "llama3_2_1b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "mamba2-130m": "mamba2_130m",
    "seamless-m4t-medium": "seamless_m4t_medium",
}

SHAPES: Dict[str, dict] = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1, long=True),
}


def _module(name: str):
    mod = ALIASES.get(name, name).replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get(name: str) -> ModelCfg:
    return _module(name).make_config()


def smoke(name: str) -> ModelCfg:
    return _module(name).make_smoke_config()


def sparse_ffn(cfg: ModelCfg, density: float) -> ModelCfg:
    """``cfg`` with every FFN swapped for the paper's static block-sparse
    FFN at ``density`` (blocks of the config's ``ffn_block_size``);
    every other width is unchanged."""
    groups = tuple(
        (tuple(dataclasses.replace(s, ffn="sparse") for s in period), rep)
        for period, rep in cfg.groups)
    return dataclasses.replace(cfg, groups=groups, ffn_density=density)


def is_native_long(cfg: ModelCfg) -> bool:
    """True when the arch handles 500k context natively (SSM state or
    hybrid with O(1)/windowed layers) -- no retained-cache approximation."""
    return cfg.family in ("ssm", "hybrid")


def input_specs(name: str, shape: str, *, cfg: ModelCfg | None = None):
    """ShapeDtypeStruct stand-ins for one (arch, shape) cell.

    Returns (kind, kwargs) where kwargs feed the corresponding launch
    entry point (train_step / prefill / serve_step).  No allocation.
    """
    cfg = cfg or get(name)
    sh = SHAPES[shape]
    b_, s = sh["batch"], sh["seq"]
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    lm = LM(cfg)

    extras = {}
    if cfg.frontend == "vision":
        extras["frontend"] = sds((b_, cfg.frontend_len, cfg.d_model),
                                 jnp.bfloat16)
    if cfg.encoder_layers:
        extras["enc_frames"] = sds((b_, cfg.frontend_len, cfg.d_model),
                                   jnp.bfloat16)

    if sh["kind"] == "train":
        batch = {"tokens": sds((b_, s), i32), "targets": sds((b_, s), i32),
                 **extras}
        return "train", {"batch": batch}

    if sh["kind"] == "prefill":
        return "prefill", {"tokens": sds((b_, s), i32), **extras}

    # decode: one token against a cache of length s
    long = sh.get("long", False)
    retained = long and not is_native_long(cfg)
    if retained:
        max_len = cfg.retained_prefix + cfg.retained_window
    else:
        max_len = s + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    memory_len = cfg.frontend_len if cfg.encoder_layers else 0
    caches = jax.eval_shape(
        lambda: lm.init_cache(b_, max_len, memory_len=memory_len))
    return "decode", {
        "tokens": sds((b_, 1), i32),
        "positions": sds((b_,), i32),
        "caches": caches,
        "retained": retained,
    }


def param_specs(name: str, *, cfg: ModelCfg | None = None):
    """ShapeDtypeStructs of the parameter pytree (no allocation)."""
    cfg = cfg or get(name)
    lm = LM(cfg)
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
