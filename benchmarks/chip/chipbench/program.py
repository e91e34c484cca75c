"""The system under test, built from a configuration file.

A configuration file names the program's preset (``program.arch``) and
states the sizes as the published ``config.json`` names them.  The preset
is cut to those sizes; a width that the file states and the preset does
not run is an error, so the cell never measures a model other than the
one its file describes.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict

from .cells import ROOT

sys.path.insert(0, str(ROOT / "src"))

# published config.json key -> the program's ArchConfig field
KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}
# sizes the program may be cut to; every other key must match the preset
CUTS = ("num_hidden_layers", "num_key_value_heads", "vocab_size")


def arch_config(config: Dict, **overrides):
    """The preset cut to the file's sizes.  ``program.smoke`` (CPU tests
    only) starts from the preset's smoke widths; ``program.dispatch`` and
    ``overrides`` set other fields of the program's config."""
    from repro.configs import get_arch
    prog = config["program"]
    base = get_arch(prog["arch"])
    if prog.get("smoke"):
        base = dataclasses.replace(base.smoke(), prefix=(),
                                   pattern=base.pattern)
    if "dispatch" in prog:
        overrides = {"dispatch": prog["dispatch"], **overrides}
    base = dataclasses.replace(base, **overrides)
    cut = {KEYS[k]: config[k] for k in CUTS if k in config}
    cfg = dataclasses.replace(base, **cut)
    for k, field in KEYS.items():
        if k in config and float(getattr(cfg, field)) != float(config[k]):
            raise ValueError(f"{config['name']}: {k}={config[k]} but the "
                             f"program runs {field}={getattr(cfg, field)}")
    if cfg.head_dim * cfg.n_heads != config["hidden_size"]:
        raise ValueError(f"{config['name']}: head_dim {cfg.head_dim}")
    if (cfg.activation, cfg.qkv_bias, cfg.tie_embeddings) != (
            "swiglu", True, config["tie_word_embeddings"]):
        raise ValueError(f"{config['name']}: the preset is not a Qwen2 block")
    return cfg


def dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]
