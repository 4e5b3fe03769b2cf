"""Architecture registry: 10 assigned archs + the paper's BERT-base.

Each `configs/<id>.py` defines `ARCH: ArchSpec` with the exact published
dims. `build_model(arch, mode)` assembles the model (LM / hybrid / enc-dec)
with every linear site resolved to dense or LUT per the paper's replacement
policy; `input_specs(arch, shape)` produces ShapeDtypeStruct stand-ins for
the four assigned input shapes (train_4k / prefill_32k / decode_32k /
long_500k).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.amm import LUTConfig, Mode
from repro.core.plan import (  # noqa: F401  (re-exported: the plan API surface)
    PAPER_DEFAULT,
    LUTPlan,
    PlanRule,
    SitePolicy,
    SiteSelector,
    SiteSpec,
    rule,
)
from repro.models import attention as attn_mod
from repro.models import encdec as encdec_mod
from repro.models import hybrid as hybrid_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import transformer as tf_mod
from repro.models.common import SiteCfg


# ---------------------------------------------------------------------------
# arch spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArchSpec:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 128
    act: str = "silu"
    mlp_gated: bool = True
    qk_norm: bool = False
    use_bias: bool = False
    causal: bool = True
    rope_theta: float = 500_000.0
    mrope_sections: tuple[int, ...] = ()
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_shared_expert: bool = False
    moe_dense_residual: bool = False
    moe_group_tokens: int = 1024        # routing-group size (section Perf M1)
    # SSM
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    conv_width: int = 4
    ssd_chunk: int = 256
    # hybrid
    attn_every: int = 0
    # enc-dec (audio)
    n_enc_layers: int = 0
    enc_frames: int = 0
    takes_embeds: bool = False       # stub frontend provides embeddings
    # LUT-NN settings (paper defaults: K=16, V aligned to site width, INT8)
    lut_k: int = 16
    lut_v: int = 32
    lut_bits: int = 8
    lut_int8_dot: bool = False          # integer one-hot contraction (section Perf)
    lut_use_kernel: bool = False        # fused Pallas v2 kernel at LUT sites (DESIGN.md §2.3)
    lut_policy: str = "all_but_first"   # or "last_n:<n>" (BERT, Fig. 13), "all"
    # First-class per-site plan (DESIGN.md §9). When set it SUBSUMES
    # lut_policy and the flat lut_* flags above; when None those legacy
    # fields are parsed into an equivalent single-rule plan (the shim), so
    # old configs/checkpoints/artifacts keep building identical models.
    lut_plan: LUTPlan | None = None
    # scale/precision policy for the production dry-run
    param_dtype: str = "float32"        # giants use bfloat16 (DESIGN.md section 5)
    kv_cache_dtype: str = "bfloat16"    # "float8_e4m3fn" halves decode cache reads
    sub_quadratic: bool = False         # eligible for long_500k
    grad_accum: int = 1                 # microbatching for the train dry-run
    notes: str = ""

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model


# ---------------------------------------------------------------------------
# shapes (assigned to all LM-family archs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}

ARCH_IDS = (
    "mamba2_370m",
    "llama3_8b",
    "minitron_8b",
    "qwen3_1p7b",
    "command_r_35b",
    "llama4_maverick_400b",
    "arctic_480b",
    "qwen2_vl_7b",
    "whisper_tiny",
    "zamba2_1p2b",
)
EXTRA_IDS = ("bert_base",)           # paper's own model, benchmarks only


def get_arch(name: str) -> ArchSpec:
    mod = importlib.import_module(f"repro.configs.{name}")
    return mod.ARCH


# ---------------------------------------------------------------------------
# arch-spec serialization (deployment artifacts, DESIGN.md §8.1)
# ---------------------------------------------------------------------------

def arch_to_dict(arch: ArchSpec) -> dict[str, Any]:
    """JSON-safe dict of every ArchSpec field (tuples become lists)."""
    out = dataclasses.asdict(dataclasses.replace(arch, lut_plan=None))
    for k, v in out.items():
        if isinstance(v, tuple):
            out[k] = list(v)
    # the plan serializes through its own schema, not dataclasses.asdict
    out["lut_plan"] = arch.lut_plan.to_dict() if arch.lut_plan is not None else None
    return out


def arch_from_dict(d: dict[str, Any]) -> ArchSpec:
    """Rebuild an ArchSpec from `arch_to_dict` output.

    Unknown keys (written by a newer repo) are ignored so old readers stay
    forward-compatible; list-valued fields are restored to tuples.
    """
    fields = {f.name: f for f in dataclasses.fields(ArchSpec)}
    kw: dict[str, Any] = {}
    for k, v in d.items():
        if k not in fields:
            continue
        if k == "lut_plan":
            kw[k] = LUTPlan.from_dict(v) if v else None
            continue
        if isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    missing = [
        n for n, f in fields.items()
        if n not in kw
        and f.default is dataclasses.MISSING
        and f.default_factory is dataclasses.MISSING
    ]
    if missing:
        raise ValueError(f"arch dict missing required fields: {missing}")
    return ArchSpec(**kw)


def all_archs() -> list[ArchSpec]:
    return [get_arch(n) for n in ARCH_IDS]


def shape_applicable(arch: ArchSpec, shape: str) -> tuple[bool, str]:
    """Whether a (arch, shape) cell runs; reason if skipped (DESIGN.md §4)."""
    if shape == "long_500k" and not arch.sub_quadratic:
        return False, "full-attention arch: 500k decode needs sub-quadratic mixing"
    return True, ""


def reduce_arch(arch: ArchSpec, **overrides: Any) -> ArchSpec:
    """Shrink an arch to a CPU-smoke-testable config of the same family.

    Keeps every structural feature (GQA ratio, qk-norm, MoE top-k, SSD,
    shared block, enc-dec, M-RoPE) while cutting width/depth/vocab.
    """
    small: dict[str, Any] = dict(
        n_layers=min(arch.n_layers, 4),
        d_model=128,
        d_ff=0 if arch.d_ff == 0 else 256,
        vocab=512,
        param_dtype="float32",
        grad_accum=1,
    )
    if arch.n_heads:
        small.update(n_heads=4, n_kv_heads=min(arch.n_kv_heads, 2) if arch.n_kv_heads < arch.n_heads else 4, d_head=32)
    if arch.n_experts:
        small.update(n_experts=4, top_k=arch.top_k)
    if arch.ssm_state:
        small.update(ssm_state=16, ssm_head_dim=16, ssd_chunk=8)
    if arch.attn_every:
        small.update(attn_every=2)
    if arch.n_enc_layers:
        small.update(n_enc_layers=2, enc_frames=8)
    if arch.mrope_sections:
        small.update(mrope_sections=(4, 6, 6))
    small.update(lut_v=16)
    small.update(overrides)
    out = dataclasses.replace(arch, **small)
    # depth cuts can strand a last_n policy past the new layer count (the
    # plan resolver validates and would rightly reject it) — clamp
    if out.lut_plan is not None:
        clamped = tuple(
            dataclasses.replace(
                r, select=dataclasses.replace(
                    r.select, n=min(r.select.n, out.n_layers),
                    # out-of-range indices pin to the new last layer (not
                    # dropped): a "first and last dense" set keeps its intent
                    layer_set=tuple(sorted({
                        min(i, out.n_layers - 1) for i in r.select.layer_set
                    })),
                )
            ) if r.select.layers in ("last_n", "set") else r
            for r in out.lut_plan.rules
        )
        out = dataclasses.replace(
            out, lut_plan=dataclasses.replace(out.lut_plan, rules=clamped)
        )
    elif out.lut_policy.startswith("last_n:"):
        n = int(out.lut_policy.split(":", 1)[1])
        if n > out.n_layers:
            out = dataclasses.replace(out, lut_policy=f"last_n:{out.n_layers}")
    return out


# ---------------------------------------------------------------------------
# replacement plan resolution
# ---------------------------------------------------------------------------

def effective_plan(arch: ArchSpec) -> LUTPlan:
    """The arch's LUTPlan: `lut_plan` when set, else the back-compat shim
    parsing `lut_policy` + the flat `lut_*` flags into a single-rule plan."""
    if arch.lut_plan is not None:
        return arch.lut_plan
    return LUTPlan.from_policy_string(
        arch.lut_policy,
        default=SitePolicy(
            k=arch.lut_k, v=arch.lut_v, bits=arch.lut_bits, per_column=False,
            int8_dot=arch.lut_int8_dot, use_kernel=arch.lut_use_kernel,
        ),
    )


# ---------------------------------------------------------------------------
# model assembly
# ---------------------------------------------------------------------------

class _PlanResolver:
    """Resolves every linear site of one build to (mode, LUTConfig).

    A site resolves to `mode` (the bundle's LUT_TRAIN/LUT_INFER) iff the
    plan replaces its (layer, kind); otherwise it stays DENSE. Dense sites
    still carry the plan-default LUTConfig as metadata (roofline/bench
    tooling reads it; params never depend on it). `layer=None` marks
    weight-shared / uniformly-stacked sites (hybrid, enc-dec), which layer
    selectors treat as matching.
    """

    def __init__(self, arch: ArchSpec, mode: Mode):
        self.arch = arch
        self.mode = mode
        self.plan = effective_plan(arch).validate(arch.n_layers)

    def _resolve(self, layer: int | None, kind: str, d_in: int,
                 lut_site: bool) -> tuple[Mode, LUTConfig]:
        cfg = None
        if lut_site and self.mode != Mode.DENSE:
            cfg = self.plan.lut_config(layer, kind, d_in, self.arch.n_layers)
        if cfg is None:
            return Mode.DENSE, self.plan.default.lut_config(d_in)
        return self.mode, cfg

    def site(self, d_in: int, d_out: int, kind: str, *,
             layer: int | None = None, lut_site: bool = True) -> SiteCfg:
        mode, cfg = self._resolve(layer, kind, d_in, lut_site)
        return SiteCfg(d_in=d_in, d_out=d_out, mode=mode, lut=cfg,
                       bias=self.arch.use_bias, name=kind)

    def expert_site(self, d_in: int, d_out: int, kind: str,
                    *, layer: int | None = None) -> moe_mod.ExpertSiteCfg:
        mode, cfg = self._resolve(layer, kind, d_in, lut_site=True)
        return moe_mod.ExpertSiteCfg(
            n_experts=self.arch.n_experts, d_in=d_in, d_out=d_out,
            mode=mode, lut=cfg,
        )


def _attn_cfg(res: _PlanResolver, *, layer: int | None = None, causal=None,
              cross=False, prefix: str = "attn") -> attn_mod.AttnCfg:
    arch = res.arch
    d, h, kv, dh = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.d_head
    return attn_mod.AttnCfg(
        d_model=d, n_heads=h, n_kv_heads=kv, d_head=dh,
        q=res.site(d, h * dh, f"{prefix}/q", layer=layer),
        k=res.site(d, kv * dh, f"{prefix}/k", layer=layer),
        v=res.site(d, kv * dh, f"{prefix}/v", layer=layer),
        o=res.site(h * dh, d, f"{prefix}/o", layer=layer),
        qk_norm=arch.qk_norm,
        rope_theta=arch.rope_theta,
        mrope_sections=arch.mrope_sections,
        causal=arch.causal if causal is None else causal,
        use_rope=not cross,
    )


def _mlp_cfg(res: _PlanResolver, *, layer: int | None = None,
             prefix: str = "mlp") -> mlp_mod.MLPCfg:
    arch = res.arch
    d, f = arch.d_model, arch.d_ff
    return mlp_mod.MLPCfg(
        d_model=d, d_ff=f,
        gate=res.site(d, f, f"{prefix}/gate", layer=layer),
        up=res.site(d, f, f"{prefix}/up", layer=layer),
        down=res.site(f, d, f"{prefix}/down", layer=layer),
        act=arch.act,
        gated=arch.mlp_gated,
    )


def _moe_cfg(res: _PlanResolver, *, layer: int | None = None) -> moe_mod.MoECfg:
    arch = res.arch
    d, f, e = arch.d_model, arch.d_ff, arch.n_experts
    return moe_mod.MoECfg(
        d_model=d, d_ff=f, n_experts=e, top_k=arch.top_k,
        # the router stays exact: approximated routing logits destabilize
        # top-k selection (DESIGN.md §4)
        router=res.site(d, e, "moe/router", layer=layer, lut_site=False),
        gate=res.expert_site(d, f, "moe/gate", layer=layer),
        up=res.expert_site(d, f, "moe/up", layer=layer),
        down=res.expert_site(f, d, "moe/down", layer=layer),
        shared=(_mlp_cfg(res, layer=layer, prefix="moe/shared")
                if arch.moe_shared_expert else None),
        act=arch.act,
        group_tokens=arch.moe_group_tokens,
    )


def _mamba_block(res: _PlanResolver, *, layer: int | None = None) -> tf_mod.BlockCfg:
    arch = res.arch
    di = arch.d_inner
    h = di // arch.ssm_head_dim
    mcfg = mamba_mod.Mamba2Cfg(
        d_model=arch.d_model, d_inner=di, n_heads=h, head_dim=arch.ssm_head_dim,
        ssm_state=arch.ssm_state, n_groups=arch.ssm_groups,
        conv_width=arch.conv_width, chunk=arch.ssd_chunk,
        in_proj=res.site(arch.d_model,
                         2 * di + 2 * arch.ssm_groups * arch.ssm_state + h,
                         "mamba/in_proj", layer=layer),
        out_proj=res.site(di, arch.d_model, "mamba/out_proj", layer=layer),
    )
    return tf_mod.BlockCfg(kind="mamba", d_model=arch.d_model, mamba=mcfg)


def _block(res: _PlanResolver, *, layer: int | None = None) -> tf_mod.BlockCfg:
    arch = res.arch
    if arch.family == "ssm":
        return _mamba_block(res, layer=layer)
    if arch.family == "moe":
        return tf_mod.BlockCfg(
            kind="moe", d_model=arch.d_model,
            attn=_attn_cfg(res, layer=layer),
            moe=_moe_cfg(res, layer=layer),
            residual_mlp=(_mlp_cfg(res, layer=layer, prefix="residual_mlp")
                          if arch.moe_dense_residual else None),
        )
    return tf_mod.BlockCfg(
        kind="dense", d_model=arch.d_model,
        attn=_attn_cfg(res, layer=layer), mlp=_mlp_cfg(res, layer=layer),
    )


def _segments(res: _PlanResolver) -> tuple[tuple[int, tf_mod.BlockCfg], ...]:
    """Resolve the plan to per-layer blocks, grouped into runs of identical
    config (jax.lax.scan segments). Non-contiguous and mixed-precision
    replacement fall out: each change of resolved block config starts a new
    segment, so e.g. dense/K16/K8/dense builds four scanned runs."""
    L = res.arch.n_layers
    if res.mode == Mode.DENSE:
        return ((L, _block(res)),)
    segs: list[list[Any]] = []
    for j in range(L):
        b = _block(res, layer=j)
        if segs and segs[-1][1] == b:
            segs[-1][0] += 1
        else:
            segs.append([1, b])
    return tuple((n, b) for n, b in segs)


# ---------------------------------------------------------------------------
# site registry (DESIGN.md §9.2)
# ---------------------------------------------------------------------------

def _mlp_site_list(m: mlp_mod.MLPCfg) -> list[tuple[str, Any, bool]]:
    sites = ([m.gate] if m.gated else []) + [m.up, m.down]
    return [(s.name, s, True) for s in sites]


def _attn_site_list(a: attn_mod.AttnCfg) -> list[tuple[str, Any, bool]]:
    return [(s.name, s, True) for s in (a.q, a.k, a.v, a.o)]


def _block_site_list(bcfg: tf_mod.BlockCfg) -> list[tuple[str, Any, bool]]:
    """(rel_path, site_cfg, goes_through_common.linear) per site of a block.

    rel_path doubles as the site kind and equals the site's param sub-tree
    path inside the block (SiteCfg.name is constructed to match); MoE expert
    sites are expert-stacked (no tape capture) so they're enumerated with
    explicit rel paths.
    """
    if bcfg.kind == "mamba":
        m = bcfg.mamba
        out = [(m.in_proj.name, m.in_proj, True), (m.out_proj.name, m.out_proj, True)]
    elif bcfg.kind == "dense":
        out = _attn_site_list(bcfg.attn) + _mlp_site_list(bcfg.mlp)
    elif bcfg.kind == "moe":
        mo = bcfg.moe
        out = _attn_site_list(bcfg.attn)
        out.append((mo.router.name, mo.router, True))
        out += [("moe/gate", mo.gate, False), ("moe/up", mo.up, False),
                ("moe/down", mo.down, False)]
        if mo.shared is not None:
            out += _mlp_site_list(mo.shared)
    else:
        raise ValueError(bcfg.kind)
    if bcfg.residual_mlp is not None:
        out += _mlp_site_list(bcfg.residual_mlp)
    return out


def _make_site_spec(path, layer, stack_index, kind, sc, tape_key) -> SiteSpec:
    return SiteSpec(
        path=path, layer=layer, stack_index=stack_index, kind=kind,
        d_in=sc.d_in, d_out=sc.d_out, bias=getattr(sc, "bias", False),
        mode=sc.mode, lut=sc.lut, tape_key=tape_key,
    )


# ---------------------------------------------------------------------------
# unified model bundle
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelBundle:
    arch: ArchSpec
    mode: Mode
    kind: str                    # "lm" | "hybrid" | "encdec"
    cfg: Any

    @property
    def param_dtype(self):
        return jnp.bfloat16 if self.arch.param_dtype == "bfloat16" else jnp.float32

    def init(self, key: jax.Array):
        if self.kind == "lm":
            return tf_mod.lm_init(key, self.cfg, dtype=self.param_dtype)
        if self.kind == "hybrid":
            return hybrid_mod.hybrid_init(key, self.cfg, dtype=self.param_dtype)
        return encdec_mod.encdec_init(key, self.cfg, dtype=self.param_dtype)

    def param_specs(self, key: jax.Array | None = None):
        k = jax.random.PRNGKey(0) if key is None else key
        return jax.eval_shape(self.init, k)

    # ---------------- site registry ----------------
    def sites(self) -> list[SiteSpec]:
        """Enumerate every linear site of this model, across all families.

        One SiteSpec per (site, layer): sites whose leaves are stacked over
        a layer run appear once per layer with the SAME `path` and
        increasing `stack_index` (consumers that act per-leaf dedupe on
        `path`). This registry replaces per-family path-string surgery in
        conversion, sharding, autotune warmup, and artifact snapshots.
        """
        out: list[SiteSpec] = []
        if self.kind == "lm":
            g = 0
            for i, (count, bcfg) in enumerate(self.cfg.segments):
                rels = _block_site_list(bcfg)
                for j in range(count):
                    for rel, sc, taped in rels:
                        out.append(_make_site_spec(
                            f"segments/{i}/{rel}", g + j, j, rel, sc,
                            f"segments/{i}/{j}/{rel}" if taped else None,
                        ))
                g += count
            if self.cfg.lm_head is not None:
                out.append(_make_site_spec(
                    "lm_head", None, None, "lm_head", self.cfg.lm_head, "lm_head"
                ))
            return out

        if self.kind == "hybrid":
            cfg = self.cfg
            rels = _block_site_list(cfg.mamba_block)
            for j in range(cfg.n_layers):
                for rel, sc, taped in rels:
                    out.append(_make_site_spec(
                        f"mamba_stack/{rel}", j, j, rel, sc,
                        f"mamba_stack/{j}/{rel}" if taped else None,
                    ))
            shared = ([(cfg.fuse.name, cfg.fuse, True)]
                      + _attn_site_list(cfg.shared_attn)
                      + _mlp_site_list(cfg.shared_mlp)
                      + [(cfg.out.name, cfg.out, True)])
            for rel, sc, taped in shared:
                out.append(_make_site_spec(
                    f"shared/{rel}", None, None, rel, sc,
                    f"shared/{rel}" if taped else None,
                ))
            return out

        # encdec: encoder layers number 0..E-1, decoder E..E+D-1 so
        # (layer, kind) stays unique model-wide
        cfg = self.cfg
        rels = _block_site_list(cfg.enc_block)
        for j in range(cfg.n_enc_layers):
            for rel, sc, taped in rels:
                out.append(_make_site_spec(
                    f"encoder/{rel}", j, j, rel, sc,
                    f"encoder/{j}/{rel}" if taped else None,
                ))
        dec = (_attn_site_list(cfg.dec_self) + _attn_site_list(cfg.dec_cross)
               + _mlp_site_list(cfg.dec_mlp))
        for j in range(cfg.n_dec_layers):
            for rel, sc, taped in dec:
                out.append(_make_site_spec(
                    f"decoder/{rel}", cfg.n_enc_layers + j, j, rel, sc,
                    f"decoder/{j}/{rel}" if taped else None,
                ))
        return out

    def lut_sites(self) -> list[SiteSpec]:
        """Registry entries that resolved to a LUT mode in this bundle."""
        return [s for s in self.sites() if s.mode != Mode.DENSE]

    # ---------------- training ----------------
    def train_logits(self, params, batch, *, compute_dtype=jnp.bfloat16):
        """Training-time forward to logits: `(logits (B,S,vocab), aux)`.

        This is the shared forward under `loss` and the teacher/student
        halves of the distillation loss (repro.train.train_step). `aux` is
        the MoE load-balance penalty for the lm family, 0 elsewhere.
        """
        if self.kind == "lm":
            pos = batch.get("pos")
            if pos is None:
                b, s = batch["labels"].shape[:2]
                pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
            logits, _, aux = tf_mod.lm_apply(
                self.cfg, params, tokens=batch.get("tokens"),
                embeds=batch.get("embeds"), pos=pos, compute_dtype=compute_dtype,
            )
            return logits, aux
        if self.kind == "hybrid":
            b, s = batch["labels"].shape
            pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
            logits, _, _ = hybrid_mod.hybrid_apply(
                self.cfg, params, tokens=batch["tokens"], pos=pos,
                compute_dtype=compute_dtype,
            )
            return logits, jnp.zeros((), jnp.float32)
        # encdec
        enc_out = encdec_mod.encode(self.cfg, params, batch["frames"],
                                    compute_dtype=compute_dtype)
        b, s = batch["labels"].shape
        pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
        logits, _ = encdec_mod.decode(
            self.cfg, params, tokens=batch["tokens"], pos=pos, enc_out=enc_out,
            compute_dtype=compute_dtype,
        )
        return logits, jnp.zeros((), jnp.float32)

    def loss_from_logits(self, logits, aux, labels):
        """CE (+ the lm family's MoE aux penalty) from `train_logits`
        output — the one place the aux weight is applied."""
        from repro.models.common import cross_entropy

        ce = cross_entropy(logits, labels)
        return ce + tf_mod.LM_AUX_WEIGHT * aux if self.kind == "lm" else ce

    def loss(self, params, batch, *, compute_dtype=jnp.bfloat16):
        logits, aux = self.train_logits(params, batch, compute_dtype=compute_dtype)
        return self.loss_from_logits(logits, aux, batch["labels"])

    # ---------------- serving ----------------
    def init_caches(self, b: int, s_max: int, *, abstract=False, dtype=jnp.bfloat16,
                    paged=None):
        """paged: an attention.PagedSpec — attention KV leaves become pooled
        {"k_pool","v_pool"} of (n_pages, page_size, KV, Dh) shared across the
        batch (DESIGN.md §12); mamba/cross leaves stay per-slot."""
        if self.kind == "lm":
            return tf_mod.init_caches(self.cfg, b, s_max, dtype, abstract=abstract,
                                      paged=paged)
        if self.kind == "hybrid":
            return hybrid_mod.hybrid_caches(self.cfg, b, s_max, dtype, abstract=abstract,
                                            paged=paged)
        return encdec_mod.encdec_caches(self.cfg, b, s_max, dtype, abstract=abstract,
                                        paged=paged)

    def forward_step(self, params, batch, caches, *, compute_dtype=jnp.bfloat16):
        """One serving step (prefill if S>1, decode if S==1).

        batch: tokens/embeds (+ optional frames for encdec prefill),
        cache_len (B,); paged caches additionally take block_tables (B, P)
        and write_len (B,). With slot_mask (B,) in the batch, every cache
        row of a slot it leaves out comes back bit-identical: dense and
        per-slot leaves are written only in its rows (paged pools are
        masked by write_len). Returns (logits for the new positions, new
        caches).
        """
        cache_len = batch["cache_len"]
        block_tables = batch.get("block_tables")
        write_len = batch.get("write_len")
        slot_mask = batch.get("slot_mask")
        if self.kind == "encdec":
            caches = dict(caches)
            if "frames" in batch:                      # prefill: run encoder
                enc_out = encdec_mod.encode(self.cfg, params, batch["frames"],
                                            compute_dtype=compute_dtype)
                cross = jax.tree.map(
                    lambda a: a.astype(compute_dtype),
                    encdec_mod.cross_kv(self.cfg, params, enc_out),
                )
                if slot_mask is not None:               # cross leaves: (L, B, ...)
                    m = slot_mask[None, :, None, None, None]
                    cross = jax.tree.map(lambda new, old: jnp.where(m, new, old),
                                         cross, caches["cross"])
                caches["cross"] = cross
            b, s = batch["tokens"].shape
            pos = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            logits, new_caches = encdec_mod.decode(
                self.cfg, params, tokens=batch["tokens"], pos=pos,
                caches=caches, cache_len=cache_len, compute_dtype=compute_dtype,
                block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
            )
            return logits, new_caches

        if self.kind == "hybrid":
            b, s = batch["tokens"].shape
            pos = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            logits, new_caches, _ = hybrid_mod.hybrid_apply(
                self.cfg, params, tokens=batch["tokens"], pos=pos,
                caches=caches, cache_len=cache_len, compute_dtype=compute_dtype,
                block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
            )
            return logits, new_caches

        tok = batch.get("tokens")
        emb = batch.get("embeds")
        ref = tok if tok is not None else emb
        b, s = ref.shape[0], ref.shape[1]
        pos = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        if self.arch.mrope_sections:
            pos = jnp.broadcast_to(pos[None], (3, b, s))
        logits, new_caches, _ = tf_mod.lm_apply(
            self.cfg, params, tokens=tok, embeds=emb, pos=pos,
            caches=caches, cache_len=cache_len, compute_dtype=compute_dtype,
            block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
        )
        return logits, new_caches


def build_model(arch: ArchSpec | str, mode: Mode | str = Mode.DENSE) -> ModelBundle:
    if isinstance(arch, str):
        arch = get_arch(arch)
    if isinstance(mode, str):
        mode = Mode(mode)
    res = _PlanResolver(arch, mode)

    if arch.family == "hybrid":
        # mamba layers share one stacked config and the attention block is
        # one weight-shared module, so sites resolve at kind granularity
        # (layer=None); layer selectors don't subdivide this family.
        d = arch.d_model
        cfg = hybrid_mod.HybridCfg(
            vocab=arch.vocab, d_model=d, n_layers=arch.n_layers,
            attn_every=arch.attn_every,
            mamba_block=_mamba_block(res),
            shared_attn=_attn_cfg(res),
            shared_mlp=_mlp_cfg(res),
            fuse=res.site(2 * d, d, "fuse", lut_site=False),
            out=res.site(d, d, "out"),
        )
        return ModelBundle(arch=arch, mode=mode, kind="hybrid", cfg=cfg)

    if arch.family == "audio":
        enc_block = tf_mod.BlockCfg(
            kind="dense", d_model=arch.d_model,
            attn=_attn_cfg(res, causal=False),
            mlp=_mlp_cfg(res),
        )
        cfg = encdec_mod.EncDecCfg(
            vocab=arch.vocab, d_model=arch.d_model,
            n_enc_layers=arch.n_enc_layers, n_dec_layers=arch.n_layers,
            enc_frames=arch.enc_frames,
            enc_block=enc_block,
            dec_self=_attn_cfg(res, causal=True, prefix="self"),
            dec_cross=_attn_cfg(res, causal=False, cross=True, prefix="cross"),
            dec_mlp=_mlp_cfg(res),
        )
        return ModelBundle(arch=arch, mode=mode, kind="encdec", cfg=cfg)

    d = arch.d_model
    cfg = tf_mod.LMCfg(
        vocab=arch.vocab, d_model=d,
        segments=_segments(res),
        lm_head=(None if arch.tie_embeddings
                 else res.site(d, arch.vocab, "lm_head", lut_site=False)),
        takes_embeds=arch.takes_embeds,
    )
    return ModelBundle(arch=arch, mode=mode, kind="lm", cfg=cfg)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def input_specs(arch: ArchSpec | str, shape: str) -> dict[str, Any]:
    """Abstract model inputs for one (arch x shape) dry-run cell."""
    if isinstance(arch, str):
        arch = get_arch(arch)
    sp = SHAPES[shape]
    b, s = sp.global_batch, sp.seq_len
    i32, bf16 = jnp.int32, jnp.bfloat16

    def tok(bb, ss):
        return jax.ShapeDtypeStruct((bb, ss), i32)

    if sp.kind == "train":
        batch: dict[str, Any] = {"labels": tok(b, s)}
        if arch.family == "vlm":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, arch.d_model), bf16)
            batch["pos"] = jax.ShapeDtypeStruct((3, b, s), i32)
        elif arch.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((b, arch.enc_frames, arch.d_model), bf16)
            batch["tokens"] = tok(b, s)
        else:
            batch["tokens"] = tok(b, s)
        return batch

    if sp.kind == "prefill":
        batch = {"cache_len": jax.ShapeDtypeStruct((b,), i32)}
        if arch.family == "vlm":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, arch.d_model), bf16)
        elif arch.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct((b, arch.enc_frames, arch.d_model), bf16)
            batch["tokens"] = tok(b, s)
        else:
            batch["tokens"] = tok(b, s)
        return batch

    # decode: one new token against a seq_len-deep cache
    batch = {"cache_len": jax.ShapeDtypeStruct((b,), i32)}
    if arch.family == "vlm":
        batch["embeds"] = jax.ShapeDtypeStruct((b, 1, arch.d_model), bf16)
    else:
        batch["tokens"] = tok(b, 1)
    return batch
