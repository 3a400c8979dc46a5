"""Clipped, privatized client updates folded into their servers per second:
rounds completed in the window x P x L over the window's seconds."""


def read(rec):
    return rec["rounds"] * rec["updates_per_round"] / rec["window_s"]
