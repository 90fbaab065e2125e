"""The Winograd convolutions' share of their roofline, in the offline cells.

Least time: for each 3x3 stride-1 conv of a batch, the larger of its
counted FLOPs over the bf16 peak and its f32 input, filter and output
bytes over the HBM bandwidth (``work.least_time``), times the batches of
the window.  Over: the device seconds of the forward Pallas kernels in
the traced window.  XLA's glue around the kernels (tile extraction,
padding, bias and ReLU) is in neither.

Both sides cover the same convolutions only while each of them reaches a
Pallas kernel.  Each such convolution runs its Winograd-domain product in
one kernel instruction of its own (``GEMM_KERNELS``); where the trace holds
fewer of those than a batch has 3x3 stride-1 convolutions, some ran
elsewhere (XLA's convolution, say), and the share is not reported.
"""

import work
import xtrace

GEMM_KERNELS = ("wino_fused_e2e", "wino_fused", "wino_gemm")
FORWARD_KERNELS = (*GEMM_KERNELS, "input_transform", "output_transform",
                   "filter_transform")


def read(view):
    if view.trace is None or not view.record.get("requests"):
        return None
    ops = view.ops(view.record["batch"])
    eligible = sum(o["eligible"] for o in ops)
    if not eligible or xtrace.instances(view.trace, GEMM_KERNELS) < eligible:
        return None
    kernel_s = xtrace.op_seconds(view.trace, FORWARD_KERNELS)
    least = work.least_time(ops, view.peak)
    return 100.0 * least * view.record["requests"] / kernel_s
