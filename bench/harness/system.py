"""The system under test, built from a configuration file and a cell's
engine settings: the served engine (`ServingEngine` behind `EnginePump`,
as `launch/serve.py` builds it for its HTTP front end) with the
benchmark's weights.

Configuration file keys are those of the model's published `config.json`
(`hidden_size`, `num_hidden_layers`, ...), plus:

    "lut": {"plan": "all_but_first", "k": 16, "v": 32, "bits": 8,
            "use_kernel": true}        (absent: the dense model)
    "compute": {"dtype": "float32", "matmul_precision": "highest"}
    "reference": "qwen3"               (module under bench/reference/)
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any

import jax
import jax.numpy as jnp

from harness import weights

# launch/serve.py's defaults for what a cell does not set
SERVE_DEFAULTS = {"max_queue": 256}


def arch(cfg: dict[str, Any]):
    """The program's ArchSpec for a configuration file; refuses a file the
    program cannot serve as stated."""
    from repro.configs import ArchSpec
    from repro.models import common

    eps = inspect.signature(common.rmsnorm).parameters["eps"].default
    if cfg["rms_norm_eps"] != eps:
        raise ValueError(f"rms_norm_eps={cfg['rms_norm_eps']}: the program "
                         f"normalises with eps={eps}")
    if cfg.get("model_type") != "qwen3" or cfg.get("attention_bias") \
            or cfg.get("use_sliding_window") or cfg.get("rope_scaling"):
        raise ValueError("only plain Qwen3 configurations are wired here")
    lut = cfg.get("lut") or {}
    return ArchSpec(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], act=cfg["hidden_act"], mlp_gated=True,
        qk_norm=True, use_bias=False, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        lut_k=lut.get("k", 16), lut_v=lut.get("v", 32), lut_bits=lut.get("bits", 8),
        lut_use_kernel=lut.get("use_kernel", False),
        lut_policy=lut.get("plan", "all_but_first"),
        param_dtype=cfg["compute"]["dtype"],
    )


@dataclasses.dataclass
class System:
    bundle: Any
    engine: Any
    pump: Any
    scale_shapes: dict
    times: dict[str, float]


def build(cfg: dict[str, Any], engine_settings: dict[str, Any], seed: int,
          clock) -> System:
    """Weights from the seed, the engine (autotune warm-up included), both
    token shapes compiled, and the pump started. `clock()` stamps each
    phase."""
    from repro.configs import build_model
    from repro.core.amm import Mode
    from repro.serving.engine import ServingEngine
    from repro.serving.server import EnginePump

    if cfg["compute"]["dtype"] != "float32":
        raise ValueError("only float32 serving is checked against the reference")
    times = {"start": clock()}
    bundle = build_model(arch(cfg), Mode.LUT_INFER if cfg.get("lut") else Mode.DENSE)
    specs = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    _, scale_shapes = weights.program_layout(cfg, specs)
    params = jax.block_until_ready(weights.program_params(cfg, seed, specs))
    times["weights"] = clock()
    kw = dict(SERVE_DEFAULTS)
    kw.update(engine_settings)
    engine = ServingEngine(bundle, params, compute_dtype=jnp.float32, **kw)
    times["engine"] = clock()
    engine.warmup()
    times["warmup"] = clock()
    return System(bundle, engine, EnginePump(engine), scale_shapes, times)
