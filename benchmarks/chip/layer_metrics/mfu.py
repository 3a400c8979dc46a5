"""Whole round's share of the chips' peak: the least time the chips need
for the round's model FLOPs at the bf16 peak over the measured time per
round.  Recomputation is not counted."""


def read(rec):
    if not rec["rounds"]:
        return None
    need_s = rec["work"]["flops_per_round"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
    return 100.0 * need_s * rec["rounds"] / rec["window_s"]
