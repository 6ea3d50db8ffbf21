"""Build the system under test from a configuration file's `program` group.

The group holds the program's own settings for this deployment: the
`ModelConfig` fields (`model`, nested groups such as `moe` as objects), the
`ServerConfig` fields a deployment fixes (`server`), and the layer pattern
(`pattern`: "paged" puts every layer on the paged full-attention path,
"default" keeps the program's OmniAttn pattern). Scheduler knobs not named
there stay at the program's defaults.
"""
from __future__ import annotations

import dataclasses

import jax


def model_config(group: dict):
    from repro.configs.base import ModelConfig
    base = ModelConfig()
    kw = {}
    for key, val in group["model"].items():
        default = getattr(base, key)
        if dataclasses.is_dataclass(default):
            val = dataclasses.replace(default, **val)
        kw[key] = val
    return ModelConfig(**kw)


def server_config(group: dict):
    from repro.serving import ServerConfig
    return ServerConfig(**group["server"])


def pattern(group: dict, n_layers: int):
    p = group.get("pattern", "default")
    if p == "paged":
        return [0] * n_layers
    if p == "default":
        return None
    raise ValueError(f"unknown pattern {p!r}")


def check_params(cfg, pat, params) -> None:
    """Raise unless `params` has the program's parameter layout: the same
    tree, shapes and types as `LM.init` would give."""
    from repro.distributed.ctx import local_mesh_ctx
    from repro.models import LM
    want = LM.build(cfg, local_mesh_ctx(), pattern=pat).shapes()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"weight tree differs from the program's: "
                         f"{jax.tree.structure(got)} vs "
                         f"{jax.tree.structure(want)}")
    for path, w, g in zip(jax.tree_util.tree_leaves_with_path(want),
                          jax.tree.leaves(want), jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path[0])}: "
                             f"{g.shape} {g.dtype}, the program takes "
                             f"{w.shape} {w.dtype}")


def build_server(group: dict, params):
    from repro.serving import Server
    cfg = model_config(group)
    pat = pattern(group, cfg.n_layers)
    check_params(cfg, pat, params)
    return Server(cfg, server_config(group), pattern=pat, params=params)
