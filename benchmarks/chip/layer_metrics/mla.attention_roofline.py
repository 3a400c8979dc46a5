"""Latent attention's causal core (q.k over 192 dims, p.v over 128) against
its roofline: the least time the chip needs for the window's forward and
backward core FLOPs (at the bf16 peak) and bytes (at the HBM peak), over
the exclusive device time under the ``mla.attention`` scope.  The recompute
of the forward under rematerialisation is in the time and not in the
count."""


def read(rec):
    t = (rec.get("scopes") or {}).get("mla.attention", 0.0)
    work = rec["work"]
    if t <= 0 or "mla_flops" not in work:
        return None
    pk = rec["peaks"]
    need = max(work["mla_flops"] / pk["bf16_flops_per_s"],
               work["mla_bytes"] / pk["hbm_bytes_per_s"]) / rec["chips"]
    return 100.0 * need / t
