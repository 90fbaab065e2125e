"""Share of the device's busy time that the Winograd convolutions spend
outside their kernels, in the offline cells.

Self seconds of the operations under the name scope of a conv whose
route is a Winograd one (``winograd...``), except those of its ``kernel``
and ``filter`` stages, over the busy seconds of the same traced batches
(``scoped.traced``: a short traced window after the cell's).  The
``epilogue`` stage (bias, batch norm, ReLU) counts, since XLA fuses it
with the crop.  A program that names no such conv reports nothing.
"""

import scoped

KERNEL_STAGES = ("kernel", "filter")


def read(view):
    if view.trace is None:
        return None
    got = scoped.traced(view)
    winograd = {key for key, algorithm, _ in got["scoped"]
                if scoped.parse_key(key) and algorithm.startswith("winograd")}
    if not winograd:
        return None
    glue = sum(t for (key, _, stage), t in got["scoped"].items()
               if key in winograd and stage not in KERNEL_STAGES)
    return 100.0 * glue / got["busy_s"]
