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
from harness.weights import lut_layers, site_dims

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


def forward_work(cfg: dict[str, Any], rows: Iterable[Row],
                 scale_shapes: dict | None = None) -> Work:
    """The least work of one forward over its valid rows only: padding rows
    and padded chunk positions count nothing; KV counts up to each row's
    valid length; the lm-head counts only rows that sample a token; every
    parameter byte is read once."""
    rows = [r for r in rows if r.new > 0]
    if not rows:
        return Work()
    t = sum(r.new for r in rows)
    n_samp = sum(1 for r in rows if r.sampled)
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    dims = site_dims(cfg)
    lut = cfg.get("lut")
    w = Work()
    for is_lut in lut_layers(cfg):
        for name, (di, do) in dims.items():
            if is_lut:
                # inputs and outputs stay on chip in the least work: only
                # the site's parameters cross HBM
                s = lut_site_work(t, di, do, lut["k"], lut["v"],
                                  scale_elems(scale_shapes, name, do))
                w += Work(s.flops, s.int8_ops, s.bytes - t * (di + do) * F32)
            else:
                w += Work(flops=2.0 * t * di * do, bytes=di * do * F32)
        w += Work(bytes=(4 * d + 2 * dh) * F32)       # norms
        for r in rows:
            # causal: position p attends to p + 1 keys
            keys = r.new * r.start + r.new * (r.new + 1) // 2
            w += Work(flops=4.0 * nh * dh * keys,
                      bytes=2 * nkv * dh * F32 * (r.start + r.new))
    w += Work(bytes=d * F32)                           # final norm
    if n_samp:
        w += Work(flops=2.0 * n_samp * d * vocab, bytes=vocab * d * F32)
    else:
        w += Work(bytes=t * d * F32)                   # embedding rows only
    return w
