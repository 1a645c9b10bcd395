"""What the benchmark takes from the program: its configuration object.

A configuration file names the program's preset and every override of it; a
traffic file adds the overrides of the job around the model. ``build_config``
applies both and then holds the result to the sizes the configuration file
states, so that a preset edited under the benchmark is caught before a run.
"""

from __future__ import annotations

import pathlib


def _set_dotted(cfg, dotted: str, value) -> None:
    obj = cfg
    *parents, leaf = dotted.split(".")
    for name in parents:
        obj = getattr(obj, name)
    if not hasattr(obj, leaf):
        raise AttributeError(f"the program's config has no {dotted!r}")
    setattr(obj, leaf, value)


def build_config(config: dict, traffic: dict, save_path: pathlib.Path, seed: int):
    """The program's ``Config`` for one cell."""
    from photon_tpu.config import load_preset

    cfg = load_preset(config["preset"])
    for overrides in (config.get("overrides", {}), traffic.get("overrides", {})):
        for key, value in overrides.items():
            _set_dotted(cfg, key, value)
    cfg.seed = int(seed) % (2 ** 31 - 1)
    cfg.photon.save_path = str(save_path)
    cfg = cfg.validate()
    for key, want in config["model"].items():
        got = getattr(cfg.model, key)
        if got != want:
            raise ValueError(
                f"configuration {config['name']!r} states model.{key}={want!r} "
                f"but the program's preset {config['preset']!r} gives {got!r}")
    return cfg


def optimizer_settings(cfg) -> dict:
    """The recipe's optimizer and schedule as plain numbers, for the plain
    reference (which imports nothing of the program)."""
    o, s = cfg.optimizer, cfg.scheduler
    if o.weight_decay or o.freeze_patterns:
        raise ValueError("the plain optimizer has no weight decay or freezing")
    return {"name": o.name, "lr": float(o.lr), "betas": tuple(o.betas),
            "eps": float(o.eps), "grad_clip_norm": float(o.grad_clip_norm),
            "schedule": s.name, "t_warmup": int(s.t_warmup),
            "t_max": int(s.t_max), "alpha_f": float(s.alpha_f)}
