"""Shape-keyed block-size + kernel-version autotuner for the LUT Pallas
kernels (DESIGN.md §3, §13).

The lut_amm kernels tile over a (N/bn, M/bm, C/bc) grid (v1/v2) or a
(N/bn, M/bm) grid with the whole codebook axis VMEM-resident (fused, v3);
the block sizes trade VMEM residency against HBM re-streaming:

  * bigger bn  -> the int8 table tile is re-read fewer times (N/bn sweeps)
  * bigger bm  -> the activation tile is re-read fewer times (M/bm sweeps)
  * bigger bc  -> fewer grid steps (less per-step overhead), bigger VMEM tiles

All three are capped by the per-step VMEM working set (`vmem_bytes`), which
must fit in 16 MB with double buffering — the budget model is documented in
DESIGN.md §3.1/§13.1 and enforced by `enumerate_candidates`.

The kernel *version* is a tunable axis alongside the block sizes
(DESIGN.md §13.2): `tune` sweeps v1 (fp32 dequant per step), v2 (int8-native
scratch accumulation) and v3 (fused encode→lookup decode,
`repro.kernels.fused_decode`) for every `lut_amm` shape and records the
winner in the cache entry (`"version"`). `kernel_choice` is the hot-path
consumer: record (measured or analytic) wins; with no record a fallback
rule applies (v1 for small-M interpret-mode shapes — the measured regime
where v2's emulation overhead loses — else the fused kernel when its
all-of-C working set fits VMEM, else v2).

Tuning modes:

  * measured  — a `measure(cfg[, version]) -> seconds` callable (real
    wall-clock on the live backend; `repro.kernels.measure` builds one:
    compiled runs, warmup + median-of-k).
  * analytic  — no accelerator present: candidates are scored with the
    roofline model in `predict_us` (HBM traffic / compute / per-step
    overhead), using the attached chip's peaks from
    `repro.roofline.analysis.chip_peaks` (the v5e's off the chip).

Winners persist to an on-disk JSON cache (DESIGN.md §3.2) keyed by
(kind, N, M, C, K, V, dtype, backend) and are consumed by `lut_amm_pallas`,
`encode_pallas`, `ops.lut_amm` dispatch, the serving engine warmup, and the
benchmarks; records carry `measured: bool` so a wall-clock winner is never
silently replaced by an analytic one (precedence: measured > artifact
snapshot > analytic — DESIGN.md §13.3). Cache path: $REPRO_AUTOTUNE_CACHE,
else ~/.cache/repro/autotune.json.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import pathlib
import tempfile
from typing import Any, Callable, Iterator

from repro.roofline.analysis import ChipPeaks, chip_peaks

# ---------------------------------------------------------------------------
# hardware model constants (DESIGN.md §3.1); the chip's peaks come from
# repro.roofline.analysis.chip_peaks, keyed by device kind
# ---------------------------------------------------------------------------

VMEM_BYTES = 16 * 2**20          # scoped VMEM a v5e kernel may use
VMEM_BUDGET = 12 * 2**20         # usable budget: leave headroom for spills
VMEM_BW_PER_HBM_BW = 8           # rough on-chip bandwidth for VPU passes
STEP_OVERHEAD_S = 1e-6           # fixed per-grid-step cost (DMA setup, sync)

_CACHE_VERSION = 1
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"

# lut_amm kernel generations swept by `tune` (DESIGN.md §13.2):
#   1 = lut_amm_pallas_v1 (fp32 dequant per codebook step)
#   2 = lut_amm_pallas    (int8-native, VMEM scratch accumulation)
#   3 = fused_decode_pallas (encode once per N tile, codes VMEM-resident)
KERNEL_VERSIONS = (1, 2, 3)
VERSION_FUSED = 3

# fallback rule threshold (no cache record): in interpret mode — the only
# mode without an accelerator to measure on — BENCH_kernels.json shows v1
# beating v2 on small-M rows (the scratch/epilogue machinery costs more than
# the dequant it saves under emulation), so small-M interpret shapes default
# to v1 rather than pinning a losing version.
_SMALL_M_V1 = 512


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One tiling choice for a fused LUT kernel."""

    block_n: int
    block_m: int
    block_c: int

    def as_dict(self) -> dict[str, int]:
        return dataclasses.asdict(self)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _divisors(c: int) -> list[int]:
    return [d for d in range(1, c + 1) if c % d == 0]


# ---------------------------------------------------------------------------
# VMEM budget model (DESIGN.md §3.1)
# ---------------------------------------------------------------------------

_LANES = 128


def _tile_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of one array as Mosaic lays it out: the minor dim padded
    to the 128 lanes, the second-minor to the sublane tile (8 rows of 32-bit
    words: 8 for fp32/int32, 16 for bf16, 32 for int8)."""
    *lead, rows, cols = shape
    sub = 8 * 4 // itemsize
    n = -(-rows // sub) * sub * (-(-cols // _LANES) * _LANES) * itemsize
    for d in lead:
        n *= d
    return n


def vmem_bytes(
    bn: int, bm: int, bc: int, k: int, v: int, *, kind: str = "lut_amm"
) -> int:
    """Per-step VMEM working set of one kernel at one tiling, in padded
    tile bytes (`_tile_bytes`): with V=32 and K=16 on the lane axis a tile
    takes 4x and 8x its element count.

    Every block is charged twice, inputs and output alike: the pipeline
    emitter double-buffers each HBM stream. On top come the scratch buffers
    and the encode temporaries that live in VMEM while the body runs: an
    fp32 activation tile and the (bc, bn, K) fp32 distances. Activations
    are charged as fp32, which bounds bf16; scales as the m-shared
    (1, 1, M) layout that `deploy_params` gives every kernel site.

    kind="fused" (DESIGN.md §13.1): bc must equal C — the fused decode
    kernel keeps the whole codebook axis resident so the encode runs once
    per N tile. It adds the int8 code scratch and the (bn, bm) int32
    contraction result, and has no accumulator.

    Checked against the v5e compiler's own scoped-VMEM sizes on 54 tilings
    of v1/v2/v3 (AOT compile for a described chip): it over-counts by
    1.3-7.7x and never under-counts, so a tiling under `VMEM_BUDGET`
    compiles; tests/test_tpu_compile.py guards the qwen3_1p7b sites.
    """
    x_tile = _tile_bytes((bn, bc, v), 4)
    p_tile = _tile_bytes((bc, k, v), 4)                  # fp32 codebook
    encode_tmp = _tile_bytes((bn, bc, v), 4) + _tile_bytes((bc, bn, k), 4)
    if kind == "encode":
        out = _tile_bytes((bn, bc), 4)                   # int32 indices
        return 2 * (x_tile + p_tile + out) + encode_tmp
    t_tile = _tile_bytes((bc, k, bm), 1)                 # int8 table
    s_tile = _tile_bytes((1, 1, bm), 4)                  # m-shared scale row
    b_tile = _tile_bytes((1, bm), 4)                     # fused bias row
    out = _tile_bytes((bn, bm), 4)                       # fp32 output tile
    blocks = 2 * (x_tile + p_tile + t_tile + s_tile + b_tile + out)
    if kind == "fused":
        codes = _tile_bytes((bn, bc, k), 1)              # int8 one-hot scratch
        return blocks + codes + encode_tmp + out         # + int32 result
    acc = out                                            # (bn, bm) scratch
    return blocks + acc + encode_tmp


def _legal_bc(bc: int, c: int, kind: str) -> bool:
    """bc is the second-minor dim of the (bn, bc, V) activation block: a
    multiple of the 8-row sublane tile, or the whole codebook axis. The
    encode kernel's (bn, bc) index output puts it on the 128 lanes too."""
    return bc % (_LANES if kind == "encode" else 8) == 0 or bc == c


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------

def predict_us(
    kind: str,
    n: int, m: int, c: int, k: int, v: int,
    bn: int, bm: int, bc: int,
    *,
    version: int = 2,
    peaks: ChipPeaks | None = None,
) -> float:
    """Roofline latency estimate (microseconds) for one tiling, on the chip
    `peaks` describes (default: `chip_peaks()`, the attached TPU's).

    HBM traffic counts tile re-streaming exactly as the BlockSpec index maps
    imply: the activation tile ignores the M grid axis (re-fetched per
    M-block revisit), the table tile ignores the N grid axis (re-fetched per
    N-block sweep), and the codebook tile is re-fetched whenever the C
    coordinate cycles. Compute charges the encode matmul per M-block (the
    fused kernel recomputes the argmin for every output tile) and the table
    contraction once; v1 additionally pays a per-step fp32 dequantization of
    the table tile on the VPU and contracts at the fp32 MXU rate, v2
    contracts int8 at the doubled int8 MXU rate (DESIGN.md §2.3). The v1
    dequant is charged additively (not under the roofline max): it is a
    serial VPU pass between the DMA and the MXU contraction that consumes
    its output, so it overlaps with neither.

    version=3 models the fused decode kernel (DESIGN.md §13.1): the encode
    matmul is charged ONCE per token (codes persist in VMEM scratch across
    the M sweep instead of being recomputed per M block), the activation
    tile is read once (its index map ignores the M axis), the codebook is
    resident for the whole grid, and codes never round-trip through HBM.
    """
    peaks = peaks or chip_peaks()
    mxu_f32, mxu_i8, hbm_bw = peaks.bf16_flops, peaks.int8_ops, peaks.hbm_bw
    gn, gm = _ceil_div(n, bn), (1 if kind == "encode" else _ceil_div(m, bm))
    gc = _ceil_div(c, bc)

    if kind != "encode" and version >= VERSION_FUSED:
        hbm = (
            n * c * v * 4                    # x read once (index map ignores M)
            + c * k * v * 4                  # codebook resident across the grid
            + c * k * m * gn                 # int8 table, re-read per N sweep
            + n * m * 4                      # output written exactly once
        )
        t_comp = (
            2.0 * n * c * v * k / mxu_f32    # encode: once, not per M block
            + 2.0 * n * c * k * m / mxu_i8   # int8 table contraction
        )
        t_steps = gn * gm * STEP_OVERHEAD_S
        return (max(hbm / hbm_bw, t_comp) + t_steps) * 1e6

    x_bytes = n * c * v * 4 * gm
    p_bytes = c * k * v * 4 * gn * gm
    enc_flops = 2.0 * n * c * v * k * gm

    t_serial = 0.0
    if kind == "encode":
        hbm = x_bytes + p_bytes + n * c * 4
        t_comp = enc_flops / mxu_f32
    else:
        t_bytes = c * k * m * gn             # int8 table, re-read per N sweep
        o_bytes = n * m * 4                  # written exactly once (v2)
        hbm = x_bytes + p_bytes + t_bytes + o_bytes
        lut_flops = 2.0 * n * c * k * m
        if version >= 2:
            t_comp = enc_flops / mxu_f32 + lut_flops / mxu_i8
        else:
            # v1: int8 -> fp32 dequant materialization per codebook step
            # (read int8 + write fp32 in VMEM), then an fp32 contraction.
            t_comp = enc_flops / mxu_f32 + lut_flops / mxu_f32
            t_serial = 5.0 * c * k * m * gn / (VMEM_BW_PER_HBM_BW * hbm_bw)

    t_mem = hbm / hbm_bw
    t_steps = gn * gm * gc * STEP_OVERHEAD_S
    return (max(t_mem, t_comp) + t_serial + t_steps) * 1e6


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------

_BN_CHOICES = (8, 16, 32, 64, 128, 256, 512)
_BM_CHOICES = (128, 256, 512, 1024)


def enumerate_candidates(
    kind: str, n: int, m: int, c: int, k: int, v: int,
    *, budget: int = VMEM_BUDGET,
) -> Iterator[BlockConfig]:
    """All tilings under the VMEM budget. Always yields at least one, except
    kind="fused", where bc is pinned to C (the whole codebook axis must be
    VMEM-resident) — an empty sweep there means the fused kernel is not a
    legal choice for this shape and the version sweep falls back to v1/v2."""
    bns = sorted({min(b, n) for b in _BN_CHOICES})
    if kind == "encode":
        bms = [0]
    else:
        bms = sorted({min(b, m) for b in _BM_CHOICES})
    bcs = [c] if kind == "fused" else [d for d in _divisors(c) if _legal_bc(d, c, kind)]
    emitted = False
    for bn in bns:
        for bm in bms:
            for bc in bcs:
                if vmem_bytes(bn, max(bm, 1), bc, k, v, kind=kind) > budget:
                    continue
                emitted = True
                yield BlockConfig(bn, bm, bc)
    if not emitted and kind != "fused":       # degenerate: smallest tiling
        yield BlockConfig(min(8, n), 0 if kind == "encode" else min(128, m), bcs[0])


def heuristic(kind: str, n: int, m: int, c: int, k: int, v: int) -> BlockConfig:
    """Cache-miss default — the pre-autotuner hardcoded tiling.

    kind="fused": bc is pinned to C; bn/bm halve until the all-of-C working
    set fits the budget (feasibility is pre-checked by `kernel_choice`)."""
    if kind == "fused":
        bn, bm = min(128, n), min(512, m)
        while bn > 8 and vmem_bytes(bn, bm, c, k, v, kind="fused") > VMEM_BUDGET:
            bn //= 2
        while bm > 128 and vmem_bytes(bn, bm, c, k, v, kind="fused") > VMEM_BUDGET:
            bm //= 2
        return BlockConfig(bn, bm, c)
    bn = min(512 if kind == "encode" else 256, n)
    bm = 0 if kind == "encode" else min(512, m)
    cap = max(1, min(c, 2048 // max(v, 1)))
    bc = max((d for d in _divisors(c) if d <= cap and _legal_bc(d, c, kind)),
             default=c)
    while bn > 8 and vmem_bytes(bn, max(bm, 1), bc, k, v, kind=kind) > VMEM_BUDGET:
        bn //= 2
    return BlockConfig(bn, bm, bc)


# ---------------------------------------------------------------------------
# on-disk cache (DESIGN.md §3.2)
# ---------------------------------------------------------------------------

def shape_key(
    kind: str, n: int, m: int, c: int, k: int, v: int,
    dtype: str, backend: str,
) -> str:
    return f"{kind}|n={n}|m={m}|c={c}|k={k}|v={v}|dtype={dtype}|backend={backend}"


def default_cache_path() -> pathlib.Path:
    env = os.environ.get(_ENV_CACHE)
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


class AutotuneCache:
    """JSON-backed winner store; safe against concurrent/partial writes."""

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = pathlib.Path(path) if path is not None else default_cache_path()
        self._entries: dict[str, dict[str, Any]] | None = None

    def load(self) -> dict[str, dict[str, Any]]:
        if self._entries is None:
            try:
                raw = json.loads(self.path.read_text())
                ok = isinstance(raw, dict) and raw.get("version") == _CACHE_VERSION
                self._entries = dict(raw["entries"]) if ok else {}
            except (OSError, ValueError, KeyError):
                self._entries = {}
        return self._entries

    def get(self, key: str) -> dict[str, Any] | None:
        return self.load().get(key)

    def put(self, key: str, record: dict[str, Any]) -> None:
        self.load()[key] = record
        _memo_clear()

    def save(self) -> None:
        payload = {"version": _CACHE_VERSION, "entries": self.load()}
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.path.parent, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise


_DEFAULT_CACHE: AutotuneCache | None = None
_MEMO: dict[str, BlockConfig] = {}
_MEMO_CHOICE: dict[str, tuple[int, BlockConfig, bool]] = {}


def get_cache() -> AutotuneCache:
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None or _DEFAULT_CACHE.path != default_cache_path():
        _DEFAULT_CACHE = AutotuneCache()
    return _DEFAULT_CACHE


def _memo_clear() -> None:
    _MEMO.clear()
    _MEMO_CHOICE.clear()


def _backend() -> str:
    import jax

    return jax.default_backend()


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def lookup(
    kind: str, n: int, m: int, c: int, k: int, v: int,
    *, dtype: str = "float32", backend: str | None = None,
    cache: AutotuneCache | None = None,
) -> BlockConfig:
    """Cheap hot-path lookup: cached winner, else the heuristic tiling.

    Never runs tuning inline — `tune` (benchmarks / engine warmup) populates
    the cache out-of-band.
    """
    backend = backend or _backend()
    key = shape_key(kind, n, m, c, k, v, dtype, backend)
    memo_key = None
    if cache is None:
        cache = get_cache()
        # memo keyed by cache path too: switching $REPRO_AUTOTUNE_CACHE
        # (e.g. per-test isolation) must not serve another cache's winners
        memo_key = f"{cache.path}|{key}"
        if memo_key in _MEMO:
            return _MEMO[memo_key]
    rec = cache.get(key)
    if rec is not None:
        cfg = BlockConfig(rec["block_n"], rec["block_m"], rec["block_c"])
    else:
        cfg = heuristic(kind, n, m, c, k, v)
    if memo_key is not None:
        _MEMO[memo_key] = cfg
    return cfg


def resolve_blocks(
    kind: str, n: int, m: int, c: int, k: int, v: int, dtype: str,
    block_n: int | None, block_m: int | None, block_c: int | None,
) -> tuple[int, int, int]:
    """Fill unspecified block sizes from the cache (or heuristic), then
    clamp to legal values for this shape — the one block-resolution path
    shared by `lut_amm_pallas` and `encode_pallas`."""
    if block_n is None or block_m is None or block_c is None:
        tuned = lookup(kind, n, m, c, k, v, dtype=dtype)
        block_n = block_n if block_n is not None else tuned.block_n
        block_m = block_m if block_m is not None else tuned.block_m
        block_c = block_c if block_c is not None else tuned.block_c
    bn = max(1, min(block_n, n))
    bm = max(1, min(block_m, m)) if m else 0
    bc = max(1, min(block_c, c))
    while c % bc:
        bc -= 1
    return bn, bm, bc


def _measure_accepts_version(measure: Callable) -> bool:
    """Whether a measure callable takes (cfg, version) or just (cfg)."""
    import inspect

    try:
        params = list(inspect.signature(measure).parameters.values())
    except (TypeError, ValueError):
        return False
    if any(p.kind == inspect.Parameter.VAR_POSITIONAL for p in params):
        return True
    positional = [p for p in params if p.kind in (
        inspect.Parameter.POSITIONAL_ONLY,
        inspect.Parameter.POSITIONAL_OR_KEYWORD,
    )]
    return len(positional) >= 2


def _rank(
    kind: str, n: int, m: int, c: int, k: int, v: int,
    versions: tuple[int, ...], score: Callable[[BlockConfig, int], float],
) -> tuple[BlockConfig | None, float, int]:
    """(tiling, score in us, version) minimizing `score` over every legal
    tiling of every version; (None, inf, versions[0]) when none scores
    finite."""
    best_cfg, best_t, best_ver = None, math.inf, versions[0]
    for ver in versions:
        cand_kind = "fused" if (kind == "lut_amm" and ver >= VERSION_FUSED) else kind
        for cand in enumerate_candidates(cand_kind, n, m, c, k, v):
            t_us = score(cand, ver)
            if t_us < best_t:
                best_cfg, best_t, best_ver = cand, t_us, ver
    return best_cfg, best_t, best_ver


def _analytic(kind, n, m, c, k, v) -> Callable[[BlockConfig, int], float]:
    peaks = chip_peaks()
    return lambda cand, ver: predict_us(
        kind, n, m, c, k, v, cand.block_n, cand.block_m, cand.block_c,
        version=ver, peaks=peaks)


def best_analytic(
    kind: str, n: int, m: int, c: int, k: int, v: int, *, version: int = 2,
) -> tuple[BlockConfig | None, float]:
    """Best roofline-scored tiling for ONE kernel version; (None, inf) when
    no legal tiling exists (fused over VMEM budget). Used by the benchmarks
    for per-version model projections without touching the cache."""
    best_cfg, best_t, _ = _rank(kind, n, m, c, k, v, (version,),
                                _analytic(kind, n, m, c, k, v))
    return best_cfg, best_t


def tune(
    kind: str, n: int, m: int, c: int, k: int, v: int,
    *, dtype: str = "float32", backend: str | None = None,
    cache: AutotuneCache | None = None,
    measure: Callable[..., float] | None = None,
    versions: tuple[int, ...] | None = None,
    save: bool = True,
) -> tuple[BlockConfig, dict[str, Any]]:
    """Pick the best (version, tiling) for one shape and persist it.

    measure: optional wall-clock callable — `(cfg, version) -> seconds`
    (or legacy `(cfg) -> seconds`); when absent the analytic `predict_us`
    model scores candidates (the only option without an accelerator).
    A measure returns inf for a tiling the compiler refuses, which then
    never wins; any other failure propagates. When every candidate is
    refused on a TPU the tuner raises; off the chip (interpret mode) it
    keeps the analytic ranking, flagged `measured: false`.

    versions: kernel generations to sweep; defaults to KERNEL_VERSIONS for
    kind="lut_amm" (v1/v2/fused is a tunable axis — DESIGN.md §13.2) and a
    single version otherwise. The winning version lands in the record.
    """
    backend = backend or _backend()
    cache = cache or get_cache()
    key = shape_key(kind, n, m, c, k, v, dtype, backend)
    if versions is None:
        versions = KERNEL_VERSIONS if kind == "lut_amm" else (2,)
    measured = measure is not None
    if measured:
        pass_version = _measure_accepts_version(measure)
        best_cfg, best_t, best_ver = _rank(
            kind, n, m, c, k, v, versions,
            lambda cand, ver: (measure(cand, ver) if pass_version
                               else measure(cand)) * 1e6)
        if not math.isfinite(best_t):
            if backend == "tpu":
                raise RuntimeError(
                    f"autotune: the TPU refused every candidate tiling of {key}")
            measured = False
    if not measured:
        best_cfg, best_t, best_ver = _rank(kind, n, m, c, k, v, versions,
                                           _analytic(kind, n, m, c, k, v))

    if best_cfg is None:
        raise RuntimeError(f"autotune: no legal tiling for {key}")
    record = {
        **best_cfg.as_dict(),
        "predicted_us": best_t,
        "measured": measured,
        "source": "wallclock" if measured else "roofline_model",
    }
    if kind == "lut_amm":
        record["version"] = best_ver
    cache.put(key, record)
    if save:
        cache.save()
    return best_cfg, record


def kernel_choice(
    n: int, m: int, c: int, k: int, v: int,
    *, dtype: str = "float32", backend: str | None = None,
    interpret: bool = False,
    cache: AutotuneCache | None = None,
) -> tuple[int, BlockConfig, bool]:
    """Hot-path (version, blocks, from_record) selection for `ops.lut_amm`.

    Precedence (DESIGN.md §13.3): the cache record — measured or analytic,
    including records restored from an artifact snapshot — always wins, so
    callers never pin a version the tuner has seen lose. Records written
    before the version axis existed (no "version" key) mean v2, the default
    those callers ran. With no record at all, the fallback rule:

      * interpret mode and M <= 512  -> v1 (BENCH_kernels.json shows v2
        losing to v1 under emulation on small-M rows);
      * fused working set fits VMEM  -> v3 (analytically dominant: encode
        runs once instead of once per M block);
      * otherwise                    -> v2.
    """
    backend = backend or _backend()
    key = shape_key("lut_amm", n, m, c, k, v, dtype, backend)
    memo_key = None
    if cache is None:
        cache = get_cache()
        memo_key = f"{cache.path}|interpret={interpret}|{key}"
        if memo_key in _MEMO_CHOICE:
            return _MEMO_CHOICE[memo_key]
    rec = cache.get(key)
    if rec is not None:
        out = (
            int(rec.get("version", 2)),
            BlockConfig(rec["block_n"], rec["block_m"], rec["block_c"]),
            True,
        )
    elif interpret and m <= _SMALL_M_V1:
        out = (1, heuristic("lut_amm", n, m, c, k, v), False)
    elif next(iter(enumerate_candidates("fused", n, m, c, k, v)), None) is not None:
        out = (VERSION_FUSED, heuristic("fused", n, m, c, k, v), False)
    else:
        out = (2, heuristic("lut_amm", n, m, c, k, v), False)
    if memo_key is not None:
        _MEMO_CHOICE[memo_key] = out
    return out
