"""Blocking-parameter model: the TPU analogue of the paper's SS3.2.2.

The paper chooses (T_blk, C_blk, K_blk) by minimizing a modeled data-movement
cost (Eq. 15) under L1/L2 capacity constraints (Eq. 10/11), with K_blk and
C_blk multiples of 16 to avoid edge cases.  On TPU the cache hierarchy
collapses to HBM->VMEM, so:

  * the capacity constraint (Eq. 10/11 analogue) is the kernel's VMEM
    working set -- streamed operand blocks (double-buffered by the Pallas
    pipeline), the f32 accumulator, and the output tile block.  The
    end-to-end fused pipeline additionally keeps a (L, T_blk, C) f32
    V-cache resident so the input transform runs once per tile block;

  * the traffic objective (Eq. 15 analogue) counts HBM bytes.  Three
    pipelines are modeled (DESIGN.md SS4):

      nonfused   bytes(V)*ceil(K/bk) + bytes(U)*ceil(T/bt) + bytes(out)
                 + 2 * 4 * L*T*K                  (O^ write + read, f32)
      fused      same minus the O^ round trip     (paper C1)
      fused_e2e  bytes(d) read ONCE (+ a small pipeline re-prime term)
                 + bytes(U)*ceil(T/bt) + bytes(out); V never exists in
                 HBM, so the bytes(V)*ceil(K/bk) re-read term and the
                 input-transform round trip (d read + V write) vanish.

  * edge-case avoidance becomes MXU/lane alignment: blocks are multiples of
    the sublane tile and the T/C/K extents are zero-padded up to block
    multiples (zero rows/columns are exact no-ops through the bilinear
    algorithm).

``choose_blocks`` enumerates the aligned candidate space and returns the
traffic-minimizing configuration -- a deterministic analytical choice, like
the paper's heuristic, not an autotuner.  It is a *mechanism*: the decision
of which pipeline/m to run lives in ``repro.core.plan`` (the single
planning layer); ``select_tile_m`` is kept as a thin back-compat wrapper
over that layer.
"""

from __future__ import annotations

import dataclasses
import functools
import math

from . import hw

PIPELINES = ("nonfused", "fused", "fused_e2e")


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    block_t: int
    block_c: int
    block_k: int
    vmem_bytes: int
    hbm_bytes_fused: int
    hbm_bytes_nonfused: int
    # End-to-end fused pipeline bytes (kernel == pipeline: the transform is
    # a GEMM prologue, so there is no separate transform-stage round trip).
    hbm_bytes_e2e: int = 0
    # Whole-pipeline bytes for the two-stage paths: kernel traffic plus the
    # input-transform round trip (d read + V write) that precedes them.
    hbm_bytes_fused_pipeline: int = 0
    hbm_bytes_nonfused_pipeline: int = 0

    def as_kwargs(self) -> dict:
        return dict(block_t=self.block_t, block_c=self.block_c, block_k=self.block_k)

    def pipeline_bytes(self, pipeline: str) -> int:
        """Modeled end-to-end HBM bytes downstream of tile extraction."""
        return {
            "nonfused": self.hbm_bytes_nonfused_pipeline,
            "fused": self.hbm_bytes_fused_pipeline,
            "fused_e2e": self.hbm_bytes_e2e,
        }[pipeline]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, mult: int) -> int:
    return _ceil_div(x, mult) * mult


def axis_candidates(size: int, granule: int, caps: tuple[int, ...]) -> list[int]:
    """Aligned candidate block sizes for one axis.

    Candidates are the ``caps`` clamped to the smallest sublane-aligned
    block covering the extent, so a candidate never exceeds the axis by
    more than one alignment step (the old logic could propose a 256 block
    for a 130-wide axis, nearly doubling padding traffic).
    """
    sub = granule if granule < 128 else 8
    limit = round_up(max(size, 1), sub)
    if size <= granule:
        return [limit]
    cands = {min(cap, limit) for cap in caps}
    return sorted(c for c in cands if c > 0)


def tile_bytes(shape: tuple[int, ...], elt: int) -> int:
    """VMEM bytes of one block: its minor two dims occupy whole
    (sublane, lane) tiles -- (8, 128) for f32, (16, 128) for bf16 -- so a
    64-wide lane dim costs what a 128-wide one does."""
    *lead, sub, lane = shape
    return (math.prod(lead) * round_up(sub, hw.SUBLANE * max(1, 4 // elt))
            * round_up(lane, hw.LANE) * elt)


def streamed_vmem_bytes(elt: int, *blocks: tuple[int, ...]) -> int:
    """VMEM of operand blocks (inputs and outputs alike) that the Pallas
    pipeline double-buffers -- the whole modelled working set of the
    standalone transform kernels and the plain GEMM."""
    return 2 * sum(tile_bytes(b, elt) for b in blocks)


def fused_vmem_bytes(L: int, m: int, bt: int, bc: int, bk: int, elt: int) -> int:
    streams = streamed_vmem_bytes(elt, (L, bt, bc), (L, bc, bk),
                                  (bt, m * m, bk))
    acc = tile_bytes((L, bt, bk), 4)          # f32 accumulator scratch
    return streams + acc


def e2e_vmem_bytes(L: int, m: int, Cp: int, bt: int, bc: int, bk: int,
                   elt: int) -> int:
    """VMEM working set of the end-to-end fused kernel (wino_fused_e2e).

    The B^T d B prologue replaces the streamed V operand with (a) the
    streamed raw-tile block d and (b) a full-C f32 V-cache, laid out
    (Cp/bc, L, bt, bc), that lets the transform run once per tile block
    and be reused across every K block.
    """
    streams = streamed_vmem_bytes(elt, (bt, L, bc), (L, bc, bk),
                                  (bt, m * m, bk))
    v_cache = tile_bytes((Cp // bc, L, bt, bc), 4)   # resident across K
    acc = tile_bytes((L, bt, bk), 4)
    return streams + v_cache + acc


def vmem_limit_bytes(model_bytes: int) -> int:
    """Scoped-VMEM limit a kernel passes to the compiler for a modelled
    working set of ``model_bytes``: the model plus headroom for the
    compiler's own temporaries (``hw.VMEM_HEADROOM_*``), capped at the
    physical VMEM.  ``choose_blocks`` never selects blocks over
    ``hw.VMEM_BUDGET``, under which the cap never cuts into the headroom."""
    headroom = max(int(model_bytes * hw.VMEM_HEADROOM_FRAC),
                   hw.VMEM_HEADROOM_MIN)
    return min(hw.VMEM_BYTES, model_bytes + headroom)


def hbm_traffic(L: int, m: int, T: int, C: int, K: int, bt: int, bk: int, elt: int,
                fused: bool) -> int:
    v = L * T * C * _ceil_div(K, bk) * elt
    u = L * C * K * _ceil_div(T, bt) * elt
    out = T * m * m * K * elt
    extra = 0 if fused else 2 * L * T * K * 4   # O^ write + read, f32
    return v + u + out + extra


def transform_stage_bytes(L: int, T: int, C: int, elt: int) -> int:
    """HBM round trip of the standalone input transform: d read + V write."""
    return 2 * L * T * C * elt


def hbm_traffic_e2e(L: int, m: int, T: int, C: int, K: int, bt: int, bc: int,
                    bk: int, elt: int) -> int:
    """End-to-end fused pipeline traffic: the tile blocks d are read once
    (the V-cache serves every K block), plus one re-prime block per tile
    block.  The kernel's d index map is (t, 0, 0) for every k > 0, so the
    only index change after the first K block is the k 0->1 transition --
    consecutive repeats are not re-fetched -- and with a single C block
    that index never changes at all."""
    d = L * T * C * elt
    reprimes = 1 if (_ceil_div(K, bk) > 1 and _ceil_div(C, bc) > 1) else 0
    reprime = L * bt * bc * elt * _ceil_div(T, bt) * reprimes
    u = L * C * K * _ceil_div(T, bt) * elt
    out = T * m * m * K * elt
    return d + reprime + u + out


# ----------------- single-pass fused backward (wino_fused_bwd) -----------------
#
# The backward mirror of the e2e constraint/objective pair.  Grid is
# (C/bc, T/bt, K/bk) with C OUTERMOST: the dU accumulator (contraction over
# the tile axis) lives in a (L, bc, Kp) block that stays VMEM-resident for
# one whole C sweep, the dV accumulator (contraction over K) is the dd
# output block itself (resident across the inner K sweep), and the V-cache
# shrinks from the forward's full-C slab to one (L, bt, bc) slice -- V is
# consumed by the dU GEMM in the same (c, t) step it is built in, so
# nothing wider ever needs to be resident.


def bwd_fused_vmem_bytes(L: int, m: int, Kp: int, bt: int, bc: int, bk: int,
                         elt: int) -> int:
    """VMEM working set of the single-pass fused backward kernel."""
    streams = (streamed_vmem_bytes(elt, (bt, L, bc), (bt, m * m, bk),
                                   (L, bc, bk))      # d, gy, U
               # outputs: dd (the dV accumulator) and the full-K dU block
               + streamed_vmem_bytes(4, (bt, L, bc), (L, bc, Kp)))
    v_slice = tile_bytes((L, bt, bc), 4)      # shared V-cache slice, f32
    do_scratch = tile_bytes((L, bt, bk), 4)   # dO^ (gy transformed once/step)
    return streams + v_slice + do_scratch


def hbm_traffic_bwd_fused(L: int, m: int, T: int, C: int, K: int, bt: int,
                          bc: int, bk: int, elt: int) -> int:
    """Single-pass backward traffic: d read once (its index map is constant
    across the inner K sweep), gy tiles re-streamed once per C block, U
    re-streamed once per tile block (as in the forward), dd and dU written
    exactly once.  No V, Gy/dO^, or intermediate dU round trip exists."""
    d = L * T * C * elt
    gy = T * m * m * K * elt * _ceil_div(C, bc)
    u = L * C * K * elt * _ceil_div(T, bt)
    dd = L * T * C * 4
    du = L * C * K * 4
    return d + gy + u + dd + du


def hbm_traffic_bwd_two_pass(L: int, m: int, T: int, C: int, K: int, bt: int,
                             bc: int, bk: int, elt: int) -> int:
    """Modeled traffic of the PR-3 two-pass backward at the same blocks.

    dx re-runs a full forward pipeline on gy (rotated filter: tile
    extraction with the a^2/m^2 halo + the e2e single-pass traffic with the
    C/K roles swapped); dw runs the standalone F(r, m) pipeline: the input
    transform's d-read + V-write round trip, the gy-side transform round
    trip, the dU GEMM streams (X~ re-read per K block, Gy re-read per C
    block -- the transposed-read BlockSpec means no materialized X~ copy is
    charged), and the dU write + read for the inverse."""
    # ---- dx: rotated-filter forward pipeline on gy ----
    dx_tiles = T * L * K * elt                       # gy halo extraction write
    dx_pipe = hbm_traffic_e2e(L, m, T, K, C, bt, bk, bc, elt)
    # ---- dw: standalone F(r, m) filter-gradient pipeline ----
    xform_v = 2 * L * T * C * elt                    # d read + V write
    xform_gy = T * m * m * K * elt + L * T * K * elt  # gy_t read + Gy write
    gemm = (L * T * C * _ceil_div(K, bk) * elt       # X~ streamed per K block
            + L * T * K * _ceil_div(C, bc) * elt)    # Gy streamed per C block
    du = 2 * L * C * K * 4                           # dU write + inverse read
    return dx_tiles + dx_pipe + xform_v + xform_gy + gemm + du


@functools.lru_cache(maxsize=None)
def choose_bwd_blocks(
    T: int,
    C: int,
    K: int,
    m: int,
    r: int,
    elt_bytes: int = 4,
    vmem_budget: int = hw.VMEM_BUDGET,
) -> BlockConfig | None:
    """Blocking for the single-pass fused backward kernel.

    Enumerates its own candidate space (the resident (L, bc, Kp) dU block
    punishes wide C blocks, and small tile blocks are cheap because only
    the U stream scales with ceil(T/bt)), minimizes the fused-backward
    traffic under the fused-backward VMEM constraint, and returns None
    when no candidate fits -- the signal for the two-pass fallback.
    """
    a = m + r - 1
    L = a * a
    t_cands = axis_candidates(T, 8, (8, 16, 32, 64, 128, 256))
    c_cands = axis_candidates(C, 128, (128, 256))
    k_cands = axis_candidates(K, 128, (128, 256))

    best: BlockConfig | None = None
    best_obj = None
    for bt in t_cands:
        for bc in c_cands:
            for bk in k_cands:
                Kp = round_up(K, bk)
                vm = bwd_fused_vmem_bytes(L, m, Kp, bt, bc, bk, elt_bytes)
                if vm > vmem_budget:
                    continue
                obj = hbm_traffic_bwd_fused(L, m, T, C, K, bt, bc, bk,
                                            elt_bytes)
                if (best is None or obj < best_obj
                        or (obj == best_obj
                            and (bt * bk) > (best.block_t * best.block_k))):
                    best = BlockConfig(
                        block_t=bt, block_c=bc, block_k=bk, vmem_bytes=vm,
                        hbm_bytes_fused=obj, hbm_bytes_nonfused=obj,
                        hbm_bytes_e2e=obj)
                    best_obj = obj
    return best


def _make_config(L: int, m: int, T: int, C: int, K: int, bt: int, bc: int,
                 bk: int, elt: int, vm: int) -> BlockConfig:
    fused = hbm_traffic(L, m, T, C, K, bt, bk, elt, fused=True)
    nonfused = hbm_traffic(L, m, T, C, K, bt, bk, elt, fused=False)
    stage = transform_stage_bytes(L, T, C, elt)
    return BlockConfig(
        block_t=bt,
        block_c=bc,
        block_k=bk,
        vmem_bytes=vm,
        hbm_bytes_fused=fused,
        hbm_bytes_nonfused=nonfused,
        hbm_bytes_e2e=hbm_traffic_e2e(L, m, T, C, K, bt, bc, bk, elt),
        hbm_bytes_fused_pipeline=fused + stage,
        hbm_bytes_nonfused_pipeline=nonfused + stage,
    )


@functools.lru_cache(maxsize=None)
def choose_blocks(
    T: int,
    C: int,
    K: int,
    m: int,
    r: int,
    elt_bytes: int = 4,
    vmem_budget: int = hw.VMEM_BUDGET,
    pipeline: str = "fused",
) -> BlockConfig | None:
    """Pick (block_t, block_c, block_k) minimizing modeled HBM traffic.

    ``pipeline`` selects the VMEM constraint and traffic objective:
    "fused" (default) and "nonfused" share the streamed-V working set;
    "fused_e2e" adds the full-C V-cache and minimizes the single-pass
    traffic.  Returns None when no candidate fits the budget: a working
    set over it would be refused by the compiler, so it is never chosen.
    """
    assert pipeline in PIPELINES, pipeline
    a = m + r - 1
    L = a * a

    t_cands = axis_candidates(T, 8, (64, 128, 256, 512))
    c_cands = axis_candidates(C, 128, (128, 256))
    k_cands = axis_candidates(K, 128, (128, 256, 512))

    best: BlockConfig | None = None
    for bt in t_cands:
        for bc in c_cands:
            for bk in k_cands:
                if pipeline == "fused_e2e":
                    Cp = round_up(C, bc)
                    vm = e2e_vmem_bytes(L, m, Cp, bt, bc, bk, elt_bytes)
                else:
                    vm = fused_vmem_bytes(L, m, bt, bc, bk, elt_bytes)
                if vm > vmem_budget:
                    continue
                cand = _make_config(L, m, T, C, K, bt, bc, bk, elt_bytes, vm)
                obj = {
                    "fused": cand.hbm_bytes_fused,
                    "nonfused": cand.hbm_bytes_nonfused,
                    "fused_e2e": cand.hbm_bytes_e2e,
                }[pipeline]
                best_obj = None if best is None else {
                    "fused": best.hbm_bytes_fused,
                    "nonfused": best.hbm_bytes_nonfused,
                    "fused_e2e": best.hbm_bytes_e2e,
                }[pipeline]
                if (
                    best is None
                    or obj < best_obj
                    or (obj == best_obj and (bt * bk) > (best.block_t * best.block_k))
                ):
                    best = cand
    return best


def select_tile_m(
    N: int, H: int, W: int, C: int, K: int, r: int = 3,
    candidates: tuple[int, ...] = (2, 4, 6),
    elt_bytes: int = 4,
) -> int:
    """F(m, r) selection policy -- the paper's C7, re-derived for TPU.

    Back-compat wrapper: the decision now lives in the ConvPlan layer
    (``repro.core.plan``), which evaluates a two-term roofline per (m,
    pipeline) candidate and caches the result per layer shape.
    """
    from .plan import ConvSpec, plan  # local import to avoid cycle

    p = plan(ConvSpec(N=N, H=H, W=W, C=C, K=K, r=r, elt_bytes=elt_bytes),
             candidates=tuple(candidates))
    return p.m if p.m is not None else candidates[0]
