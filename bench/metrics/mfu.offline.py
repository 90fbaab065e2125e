"""Whole forward's share of the chip's peak in the offline cells.

Counted FLOPs per image (``work.forward_work``: Winograd F(6,3)-domain
multiply-adds for 3x3 stride-1 convs, direct FLOPs for the rest) times the
traced window's images per second, over chips times the bf16 peak.
"""

import work


def read(view):
    rec = view.record
    if not rec.get("images"):
        return None
    per_image = work.forward_work(view.cell.ref, view.cell.cfg, 1)["counted_flops"]
    rate = rec["images"] / rec["window_s"]
    return 100.0 * per_image * rate / (view.chips * view.peak["bf16_flops_per_s"])
