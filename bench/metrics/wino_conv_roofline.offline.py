"""The 3x3 stride-1 convolutions' share of their roofline, glue included,
in the offline cells.

Least time: as in ``conv_roofline.offline``, ``work.least_time`` of the
eligible convs of a batch, times the batches traced.  Over: the self
seconds of every operation under the name scope of a 3x3 stride-1 conv,
whatever route the planner chose for it: its tile extraction, padding,
transforms, kernels, crop and epilogue.  So moving a conv off the
Winograd kernels cannot raise the share by leaving the denominator.  Both
are read from a short traced window after the cell's (``scoped.traced``).

Reported only when every eligible conv shape of the reference forward
appears as a conv scope in the trace.
"""

import scoped
import work


def read(view):
    if view.trace is None or not view.record.get("requests"):
        return None
    ops = view.ops(view.record["batch"])
    eligible = {scoped.conv_key(r, 1, C, K, H, W)
                for o in ops if o["eligible"]
                for _, H, W, C, K, r, _, _ in [o["shape"]]}
    got = scoped.traced(view)
    if not eligible or not eligible <= {key for key, _, _ in got["scoped"]}:
        return None
    conv_s = sum(t for (key, _, _), t in got["scoped"].items()
                 if (scoped.parse_key(key) or ())[:2] == (3, 1))
    least = work.least_time(ops, view.peak)
    return 100.0 * least * got["batches"] / conv_s
