"""The models a replica can be started on, by preset name.

One place looks a `--preset` up (models/serving_http.py, and through it
`tony serve`): the `PRESETS` of every module listed here, read when asked, so
a configuration registered in a module's `PRESETS` before the server starts
(the benchmark's families do) is found. A module listed here has `PRESETS`,
`init(key, cfg)` and `serving_programs(cfg, kv)` (models/serving.py).
"""

from __future__ import annotations

import importlib
import sys

#: modules a replica can serve. mixtral is not among them: `serving_http` draws and loads no weights for
#: it (the benchmark's families/mixtral.serve_install raises) and its decode computes every expert for every
#: row (generate._ffn_with_cache); a routed FFN is served by exaone_moe, whose decode multiplies the chosen
#: experts only
SERVABLE = ("tony_tpu.models.llama", "tony_tpu.models.minicpm_sala", "tony_tpu.models.exaone_moe",
            "tony_tpu.models.dots3_note", "tony_tpu.models.mistral4", "tony_tpu.models.olmo_hybrid",
            "tony_tpu.models.granite_hybrid", "tony_tpu.models.solar_open2", "tony_tpu.models.falcon_h1",
            "tony_tpu.models.phi4_flash")


def presets() -> dict:
    """preset name -> config object, over every servable module; a name two
    modules give is an error."""
    out: dict = {}
    for name in SERVABLE:
        for preset, cfg in importlib.import_module(name).PRESETS.items():
            if preset in out:
                raise ValueError(f"preset {preset!r} is registered by {name} and by {type(out[preset]).__module__}")
            out[preset] = cfg
    return out


def module_of(cfg):
    """The model module that defines a config object's class."""
    return sys.modules[type(cfg).__module__]
