"""Exclusive device milliseconds per round of the step's ops under the
program's expert-layer scopes (``moe.route``, ``moe.dispatch``,
``moe.experts``, ``moe.combine``, ``moe.shared``), forward and backward,
from the traced window and the step's compiled HLO."""

MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
              "moe.shared")


def read(rec):
    scopes = rec.get("scopes")
    if not scopes or not rec["rounds"]:
        return None
    s = sum(scopes.get(k, 0.0) for k in MOE_SCOPES)
    return 1e3 * s / rec["rounds"] if s > 0 else None
