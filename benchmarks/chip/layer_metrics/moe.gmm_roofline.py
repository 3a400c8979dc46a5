"""The held experts' grouped matmuls against their roofline: the least
time the chip needs for their forward and backward FLOPs (at the bf16
peak) and bytes (at the HBM peak), for the tokens the step counted as
routed to the held experts in the window, over the exclusive device time
under the ``moe.experts`` scope.  The recompute of the forward under
rematerialisation is in the time and not in the count."""


def read(rec):
    t = (rec.get("scopes") or {}).get("moe.experts", 0.0)
    work = rec["work"]
    if t <= 0 or "gmm_flops" not in work:
        return None
    pk = rec["peaks"]
    need = max(work["gmm_flops"] / pk["bf16_flops_per_s"],
               work["gmm_bytes"] / pk["hbm_bytes_per_s"]) / rec["chips"]
    return 100.0 * need / t
