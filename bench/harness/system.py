"""The system under test, built from a configuration file and a cell's
engine settings: the served engine (`ServingEngine` behind `EnginePump`,
as `launch/serve.py` builds it for its HTTP front end) with the
benchmark's weights.

Configuration file keys are those of the model's published `config.json`
(`model_type`, `hidden_size`, `num_hidden_layers`, ...), plus:

    "lut": {"plan": "all_but_first", "k": 16, "v": 32, "bits": 8,
            "use_kernel": true}        (absent: the dense model)
    "compute": {"dtype": "float32", "matmul_precision": "highest"}
    "reference": "<name>"              (module under bench/reference/)

The family module that `model_type` names (`harness.family`) gives the
program's ArchSpec and the layout of its parameters.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from harness import weights

# launch/serve.py's defaults for what a cell does not set
SERVE_DEFAULTS = {"max_queue": 256}


@dataclasses.dataclass
class System:
    model: Any                     # harness.family.Model
    bundle: Any
    engine: Any
    pump: Any
    scale_shapes: dict
    times: dict[str, float]


def build(model, engine_settings: dict[str, Any], seed: int, clock) -> System:
    """Weights from the seed, the engine (autotune warm-up included), both
    token shapes compiled, and the pump started. `clock()` stamps each
    phase."""
    from repro.configs import build_model
    from repro.core.amm import Mode
    from repro.serving.engine import ServingEngine
    from repro.serving.server import EnginePump

    cfg = model.cfg
    if cfg["compute"]["dtype"] != "float32":
        raise ValueError("only float32 serving is checked against the reference")
    times = {"start": clock()}
    bundle = build_model(model.family.arch(cfg),
                         Mode.LUT_INFER if cfg.get("lut") else Mode.DENSE)
    specs = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    _, scale_shapes = weights.program_layout(model, specs)
    params = jax.block_until_ready(weights.program_params(model, seed, specs))
    times["weights"] = clock()
    kw = dict(SERVE_DEFAULTS)
    kw.update(engine_settings)
    engine = ServingEngine(bundle, params, compute_dtype=jnp.float32, **kw)
    times["engine"] = clock()
    engine.warmup()
    times["warmup"] = clock()
    return System(model, bundle, engine, EnginePump(engine), scale_shapes, times)
