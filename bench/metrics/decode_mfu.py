"""The decode step's roofline share (device trace), in %: for each traced
step max(required FLOPs / peak FLOP/s, required bytes / peak bytes/s),
summed, over the summed device time of those steps."""


def read(rec):
    tr = rec["trace"]
    mods = tr and tr["module_s"]["decode"]
    if not mods or not rec["peaks"]:
        return None
    pk = rec["peaks"]
    steps = rec["work"]["decode_steps"][:len(mods)]
    need = sum(max(s["flops"] / pk["bf16_flops_per_s"],
                   s["bytes"] / pk["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * need / sum(mods[:len(steps)])
