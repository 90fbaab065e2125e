"""Target-hardware constants (TPU v5e) used by blocking + roofline models.

The container executes on CPU; these constants describe the *target* the
kernels and the dry-run roofline are modeled against (assignment spec):

  peak bf16 matmul     : 197 TFLOP/s per chip
  HBM bandwidth        : 819 GB/s per chip
  ICI link bandwidth   : ~50 GB/s per link
  VMEM                 : 128 MiB per core.  Mosaic caps a kernel's scoped
                         VMEM (pipeline buffers + its own temporaries) at
                         16 MiB unless the kernel passes
                         ``vmem_limit_bytes``; the v5e compiler accepts a
                         limit up to the full 128 MiB, and scratch_shapes
                         come out of the same 128 MiB.
"""

PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
PEAK_FLOPS_F32 = PEAK_FLOPS_BF16 / 2  # MXU f32 rate is half of bf16
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
VMEM_BYTES = 128 * 2**20          # physical VMEM == largest accepted limit
# A kernel's scoped-VMEM limit is its modelled working set plus headroom
# for what the model does not count: the compiler's own temporaries (the
# unrolled transforms keep ~alpha^2 vectors of a block live).  Compiles for
# a described v5e measured them at up to +21% of the model on the Table-1
# layers.  See ``blocking.vmem_limit_bytes``.
VMEM_HEADROOM_FRAC = 0.5
VMEM_HEADROOM_MIN = 4 * 2**20
# Largest modelled working set the blocking may choose.  With headroom its
# limit stays within VMEM_BYTES; half the VMEM also keeps one kernel's
# Mosaic compile to seconds (blocks modelled at ~90 MiB took 35-40 s).
VMEM_BUDGET = VMEM_BYTES // 2

MXU_DIM = 128                     # systolic array edge
SUBLANE = 8                       # f32 sublane tile
LANE = 128                        # lane tile

# single-pod / multi-pod mesh shapes used throughout
POD_MESH = (16, 16)               # ("data", "model") = 256 chips
MULTIPOD_MESH = (2, 16, 16)       # ("pod", "data", "model") = 512 chips
