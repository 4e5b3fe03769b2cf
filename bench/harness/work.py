"""The work a step requires, counted from shapes: the algorithm's work,
the same whatever implements it.

A LUT site on N rows (d_in = C * v, K centroids, M outputs):
  encode          2 * N * C * K * v   flops   (a distance per centroid)
  lookup-accumulate   N * C * M       int8 ops (one table entry per code)
  bytes: the int8 table, the centroids, the scales and any bias, the input
  and the output, each read or written once.
It is never counted at the one-hot MXU cost (N * C * K * M): that is one
implementation's price, not the algorithm's.

A dense site: 2 * N * d_in * d_out flops; its weight read once.

The least time of some work is the larger of its operations over the peak
for their kind and its bytes over the HBM bandwidth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable

from harness.peaks import Peaks
from harness.weights import Layer, lut_layers

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    int8_ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.int8_ops + o.int8_ops,
                    self.bytes + o.bytes)

    def least_s(self, p: Peaks) -> float:
        compute = self.flops / p.flops + self.int8_ops / p.int8_ops
        return max(compute, self.bytes / p.hbm_bytes)

    def bound(self, p: Peaks) -> str:
        compute = self.flops / p.flops + self.int8_ops / p.int8_ops
        return "compute" if compute >= self.bytes / p.hbm_bytes else "memory"


def lut_site_bytes(n: int, d_in: int, d_out: int, k: int, v: int,
                   scale_elems: int) -> dict[str, int]:
    """The bytes one LUT site on n rows reads or writes once, by operand
    (activations in float32)."""
    c = d_in // v
    return {"input": n * d_in * F32, "centroids": c * k * v * F32,
            "table": c * k * d_out, "scales": scale_elems * F32,
            "output": n * d_out * F32}


def lut_site_work(n: int, d_in: int, d_out: int, k: int, v: int,
                  scale_elems: int, bias: bool = False) -> Work:
    """One LUT site on n rows, activations in float32."""
    c = d_in // v
    moved = sum(lut_site_bytes(n, d_in, d_out, k, v, scale_elems).values())
    moved += d_out * F32 if bias else 0
    return Work(flops=2.0 * n * c * k * v, int8_ops=float(n) * c * d_out,
                bytes=moved)


def dense_site_work(n: int, d_in: int, d_out: int) -> Work:
    return Work(flops=2.0 * n * d_in * d_out,
                bytes=d_in * d_out * F32 + n * (d_in + d_out) * F32)


def scale_elems(scale_shapes: dict | None, name: str, d_out: int) -> int:
    """Elements of a LUT site's table scales, as the program lays them out
    (one per output column where the layout is not known)."""
    shape = scale_shapes.get(name) if scale_shapes else None
    if shape is None:
        return d_out
    n = 1
    for x in shape:
        n *= x
    return n


@dataclasses.dataclass(frozen=True)
class Row:
    """One slot's part of a forward: `new` tokens written at positions
    start..start+new-1, and whether a token is sampled from its last."""

    start: int
    new: int
    sampled: bool


def sites_work(spec: Layer, lut: dict[str, Any] | None, t: int,
               scale_shapes: dict | None = None) -> Work:
    """The least work of one layer's linear sites (`spec`) on t token rows,
    served as LUTs with the configuration's LUT settings `lut` (None:
    dense). Inputs and outputs stay on chip in the least work: only the
    sites' parameters cross HBM.

    An expert-stacked site on t tokens serves t * top_k (token, expert)
    pairs and reads the parameters of the experts they can hit,
    min(experts, t * top_k), once. Its experts share one set of centroids,
    so a LUT expert site encodes each token once."""
    w = Work()
    for s in spec.sites:
        di, do, e = s.d_in, s.d_out, max(s.experts, 1)
        pairs = t * s.top_k if s.experts else t
        held = min(s.experts, pairs) if s.experts else 1
        if lut is None or not s.lut:
            w += Work(flops=2.0 * pairs * di * do, bytes=held * di * do * F32)
            continue
        k, v = lut["k"], lut["v"]
        c = di // v
        parts = lut_site_bytes(t, di, do, k, v, scale_elems(scale_shapes, s.name, do * e) // e)
        w += Work(flops=2.0 * t * c * k * v, int8_ops=float(pairs) * c * do,
                  bytes=parts["centroids"] + held * (parts["table"] + parts["scales"]))
    return w


@dataclasses.dataclass(frozen=True)
class Attention:
    """One layer's attention, as its family lays it out: the heads, the
    width of a score and of a value per head, and the bytes the cache holds
    for one position."""

    heads: int
    qk_dim: int
    v_dim: int
    cached_bytes: int


def attention_work(rows: Iterable[Row], a: Attention) -> Work:
    """One layer's attention over each row's keys, causal (position p
    attends to p + 1 keys): a score of qk_dim and a weighted sum of v_dim
    per head and key, and the cache read up to each row's valid length."""
    w = Work()
    for r in rows:
        keys = r.new * r.start + r.new * (r.new + 1) // 2
        w += Work(flops=2.0 * a.heads * (a.qk_dim + a.v_dim) * keys,
                  bytes=a.cached_bytes * (r.start + r.new))
    return w


def forward_work(model, rows: Iterable[Row], scale_shapes: dict | None = None) -> Work:
    """The least work of one forward of `model` (a `family.Model`) over its
    valid rows only: padding rows and padded chunk positions count nothing;
    each layer's sites (`sites_work`), norms and attention (the family's
    `attention(cfg)`); KV up to each row's valid length; the lm-head only
    for rows that sample a token; every parameter byte read once."""
    rows = [r for r in rows if r.new > 0]
    if not rows:
        return Work()
    cfg = model.cfg
    t = sum(r.new for r in rows)
    n_samp = sum(1 for r in rows if r.sampled)
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    attn = model.family.attention(cfg)
    w = Work()
    for spec, is_lut in zip(model.layers(), lut_layers(cfg)):
        w += sites_work(spec, cfg.get("lut") if is_lut else None, t, scale_shapes)
        w += Work(bytes=sum(n for _, n in spec.norms) * F32)
        w += attention_work(rows, attn)
    w += Work(bytes=d * F32)                           # final norm
    if n_samp:
        w += Work(flops=2.0 * n_samp * d * vocab, bytes=vocab * d * F32)
    else:
        w += Work(bytes=t * d * F32)                   # embedding rows only
    return w
